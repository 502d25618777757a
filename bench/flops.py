"""Model FLOPs of a training step, from the configuration's shapes.

Per token: 6 x the parameters of every matrix product (the embedding
gather counts none; a tied head counts once, as the head), plus causal
attention's score and value products, 3 x 2 * S * H * dh per layer (a causal
row sees S/2 keys on average, forward and twice backward). A sliding window
shorter than the sequence cuts the keys a row sees. Recomputation counts
none.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return m["num_hidden_layers"] * per_layer + d * m["vocab_size"]


def attention_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward score and value products per token, causal."""
    window = m.get("sliding_window") or seq_len
    # mean keys seen per row: S/2 for a full causal mask, about the window
    # once the window is shorter than the sequence
    keys = seq_len / 2 if window >= seq_len else window * (1 - window / (2 * seq_len))
    per_layer = 3 * 2 * 2 * keys * m["num_attention_heads"] * m["head_dim"]
    return m["num_hidden_layers"] * per_layer


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 6 * matmul_params(m) + attention_flops_per_token(m, seq_len)
