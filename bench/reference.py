"""Plain float32 references that decide ``correct``.

Everything here is written from the published architecture and from the
training recipe the benchmark hands the program (its traffic file), in
straightforward ``jax.numpy``. It imports nothing of the program and takes
nothing the program made: weights, batches and optimizer state are all
regenerated from the run's seed.

* :func:`lm_readings` trains a decoder-only LM (GQA attention with RoPE and
  an optional sliding window, SwiGLU MLP, RMSNorm, tied or untied head) for
  a few AdamW steps and returns what the train cells compare: each step's
  loss, the per-leaf norms of the first gradient as AdamW applies it
  (clipped), and the per-leaf norms of the parameters' change.
* :func:`attention` is causal GQA softmax attention for the tune cell.

Matrix products run at ``Precision.HIGHEST`` (a TPU otherwise rounds float32
operands through bfloat16). ``numerics="fp8"`` is the control: every matrix
product takes operands rounded to float8 (e4m3 forward, e5m2 cotangents,
per-tensor scaling), the step below the bfloat16 the train cells state;
``"bf16"`` computes in bfloat16, the step below the tune cell's float32.
``fault="half_batch"`` leaves out half of each batch and takes the mean over
the rest.

The program's weight decay reaches every leaf of rank two or more as the
leaf is stored, stacked over layers, which includes the norm scales and
biases; the reference stores and decays them the same way.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: tokens per block of the loss, so the logits over a large vocabulary are
#: never all held at once
LOSS_ROWS = 512
#: query rows per block of attention
ATTN_ROWS = 512


# ---------------------------------------------------------------------------
# Inputs: the synthetic token stream, step by step
# ---------------------------------------------------------------------------


def _mix(a: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser."""
    a = (a + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    a ^= a >> np.uint64(30)
    a = (a * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
    a ^= a >> np.uint64(27)
    a = (a * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
    return a ^ (a >> np.uint64(31))


def token_batch(*, vocab: int, seq_len: int, batch: int, seed: int,
                step: int, noise: float) -> Dict[str, np.ndarray]:
    """Batch ``step`` of the noisy affine bigram stream
    ``x[t+1] = 3 x[t] + 7 (mod vocab)``, with a ``noise`` share of tokens
    drawn by hash; rows and steps differ by their hash seeds."""
    rows = np.arange(batch).astype(np.uint64)
    base = _mix(rows[:, None] * np.uint64(1_000_003)
                + np.uint64(step) * np.uint64(7_919)
                + np.uint64(seed) * np.uint64(104_729))
    toks = np.empty((batch, seq_len + 1), np.int64)
    toks[:, 0] = base[:, 0] % vocab
    h = base[:, 0]
    for t in range(1, seq_len + 1):
        h = _mix(h + np.uint64(t))
        rand_tok = (h % np.uint64(vocab)).astype(np.int64)
        is_noise = ((h >> np.uint64(40)).astype(np.float64) / float(2 ** 24)
                    < noise)
        toks[:, t] = np.where(is_noise, rand_tok, (toks[:, t - 1] * 3 + 7) % vocab)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "targets": toks[:, 1:].astype(np.int32)}


# ---------------------------------------------------------------------------
# Numerics: float32 at highest precision, or the float8 control
# ---------------------------------------------------------------------------


def _round_scaled(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return ((x * scale).astype(dtype).astype(jnp.float32) / scale).astype(x.dtype)


@jax.custom_vjp
def _fp8(x):
    return _round_scaled(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_scaled(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def make_dot(numerics: str):
    """``dot(spec, a, b)``: an einsum at the chosen numerics."""
    if numerics == "f32":
        def dot(spec, a, b):
            return jnp.einsum(spec, a, b, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
    elif numerics == "bf16":
        def dot(spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.bfloat16)
    elif numerics == "fp8":
        def dot(spec, a, b):
            return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST,
                              preferred_element_type=jnp.float32)
    else:
        raise ValueError(f"unknown numerics {numerics!r}")
    return dot


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


def _trunc_normal(key, shape, fan_in):
    return (1.0 / math.sqrt(fan_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def init_params(m: dict, seed: int) -> dict:
    """Truncated-normal weights with 1/sqrt(fan_in) scale, unit norm scales,
    zero biases, drawn from ``seed`` in the order the program draws them:
    the seed's key splits into 3 + layers keys (embedding, head, unused,
    then one per layer); a layer's key splits into attention and MLP keys,
    and those into one key per matrix."""
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv, dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    n_layers, V = m["num_hidden_layers"], m["vocab_size"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3 + n_layers)
    params = {"embed": _trunc_normal(keys[0], (V, d), d),
              "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}
    if not m["tie_word_embeddings"]:
        params["head"] = _trunc_normal(keys[1], (d, V), d)

    def layer(key):
        k_attn, k_mlp = jax.random.split(key)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        mixer = {"wq": _trunc_normal(ka[0], (d, h, dh), d),
                 "wk": _trunc_normal(ka[1], (d, kv, dh), d),
                 "wv": _trunc_normal(ka[2], (d, kv, dh), d),
                 "wo": _trunc_normal(ka[3], (h, dh, d), h * dh)}
        if m["attention_bias"]:
            mixer.update(bq=jnp.zeros((h, dh)), bk=jnp.zeros((kv, dh)),
                         bv=jnp.zeros((kv, dh)))
        return {"norm1": {"scale": jnp.ones((d,))},
                "mixer": mixer,
                "norm2": {"scale": jnp.ones((d,))},
                "mlp": {"w_gate": _trunc_normal(km[0], (d, f), d),
                        "w_up": _trunc_normal(km[1], (d, f), d),
                        "w_down": _trunc_normal(km[2], (f, d), f)}}

    layers = [layer(keys[3 + i]) for i in range(n_layers)]
    params["blocks"] = {"pos0": jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *layers)}
    return params


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary embedding over positions 0..S-1; x (B,S,H,dh)."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, *, dot, window: Optional[int] = None, rows: int = ATTN_ROWS):
    """Causal softmax attention with GQA, in blocks of query rows.
    q (B,S,H,dh), k/v (B,S,K,dh) -> (B,S,H,dh)."""
    B, S, H, dh = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    rows = min(rows, S)
    assert S % rows == 0, (S, rows)
    k_pos = jnp.arange(S)[None, :]

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = dot("bqhd,bkhd->bhqk", qb, k) * dh ** -0.5
        q_pos = i * rows + jnp.arange(rows)[:, None]
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return dot("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // rows))  # (n, B, rows, H, dh)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, dh)


def loss_fn(params, tokens, targets, m: dict, dot):
    """Mean token cross-entropy over the vocabulary."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    window = m.get("sliding_window")
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        a = p["mixer"]
        hn = _rmsnorm(x, p["norm1"]["scale"], eps)
        q = dot("bsd,dhk->bshk", hn, a["wq"])
        k = dot("bsd,dhk->bshk", hn, a["wk"])
        v = dot("bsd,dhk->bshk", hn, a["wv"])
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        o = attention(_rope(q, theta), _rope(k, theta), v, dot=dot, window=window)
        x = x + dot("bshk,hkd->bsd", o, a["wo"])
        hn = _rmsnorm(x, p["norm2"]["scale"], eps)
        mp = p["mlp"]
        g = jax.nn.silu(dot("bsd,df->bsf", hn, mp["w_gate"]))
        u = dot("bsd,df->bsf", hn, mp["w_up"])
        return x + dot("bsf,fd->bsd", g * u, mp["w_down"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["pos0"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    w = params["head"] if "head" in params else params["embed"].T
    n = x.shape[0] * x.shape[1]
    rows = min(LOSS_ROWS, n)
    xs = x.reshape(n // rows, rows, -1)
    ts = targets.reshape(n // rows, rows)

    @jax.checkpoint
    def block_sum(xb, tb):
        logits = dot("nd,dv->nv", xb, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total, _ = jax.lax.scan(lambda c, b: (c + block_sum(*b), None),
                            jnp.zeros((), jnp.float32), (xs, ts))
    return total / n


# ---------------------------------------------------------------------------
# AdamW, as the traffic file states it
# ---------------------------------------------------------------------------


def learning_rate(opt: dict, count):
    c = count.astype(jnp.float32)
    warm = jnp.minimum(c / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((c - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    decay = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return opt["learning_rate"] * warm * decay


def adamw(params, grads, state, opt: dict):
    """Global-norm clipping, Adam moments with bias correction, decoupled
    weight decay on leaves of rank two or more."""
    count = state["count"] + 1
    lr = learning_rate(opt, count)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["beta1"], opt["beta2"]
    c = count.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + opt["eps"])
        if p.ndim >= 2:
            step = step + opt["weight_decay"] * p
        return p - lr * step, m, v

    out = jax.tree_util.tree_map(upd, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "count": count}


# ---------------------------------------------------------------------------
# Readings
# ---------------------------------------------------------------------------


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """Norm of each leaf, one per layer for leaves stacked under ``blocks``,
    keyed by the leaf's path."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        axes = tuple(range(1, x.ndim)) if "blocks" in name else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))
    return out


def flat_norms(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    """``{path: array}`` -> ``{path[i]: float}`` with one entry per layer."""
    flat = {}
    for name, arr in norms.items():
        arr = np.asarray(arr, np.float64)
        if arr.ndim == 0:
            flat[name] = float(arr)
        else:
            flat.update({f"{name}[{i}]": float(a) for i, a in enumerate(arr)})
    return flat


class Static(tuple):
    """A dict frozen into a hashable tuple of items, so that a configuration
    can be a static argument of ``jax.jit``; ``.d`` gives the dict back."""

    def __new__(cls, d: dict):
        return super().__new__(cls, sorted(d.items()))

    @property
    def d(self) -> dict:
        return dict(self)


@functools.partial(jax.jit, static_argnums=(0,))
def _init(m: Static, seed):
    return init_params(m.d, seed)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(4, 5))
def _step(m: Static, opt: Static, numerics: str, fault, params, state, batch):
    tokens, targets = batch["tokens"], batch["targets"]
    if fault == "half_batch":
        half = tokens.shape[0] // 2
        tokens, targets = tokens[:half], targets[:half]
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, m.d,
                                              make_dot(numerics))
    params, state = adamw(params, grads, state, opt.d)
    return params, state, loss, leaf_norms(state["m"])


@functools.partial(jax.jit, static_argnums=(0,))
def _change(m: Static, params, seed):
    return leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, params, init_params(m.d, seed)))


def lm_readings(m: dict, opt: dict, batches, seed: int, *,
                numerics: str = "f32", fault: Optional[str] = None) -> dict:
    """Train ``len(batches)`` AdamW steps from the seed's weights.

    Returns ``{"loss": [per step], "grad": {leaf: norm of the step-0
    gradient as AdamW applied it, clipped: its first moment after one step
    over 1 - beta1}, "change": {leaf: norm of the parameters' change}}``."""
    if opt.get("factored") or opt.get("state_dtype", "f32") != "f32":
        raise ValueError("the reference's AdamW keeps float32, unfactored moments")
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers", "vocab_size",
            "tie_word_embeddings", "attention_bias",
            "rms_norm_eps", "rope_theta", "sliding_window")
    ms = Static({k: m.get(k) for k in keys})
    os_ = Static({k: v for k, v in opt.items() if k not in ("factored", "state_dtype")})
    with jax.default_matmul_precision("highest"):
        params = _init(ms, seed)
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
        state = {"m": zeros(), "v": zeros(), "count": jnp.zeros((), jnp.int32)}
        losses, grad = [], None
        for i, b in enumerate(batches):
            params, state, loss, mn = _step(
                ms, os_, numerics, fault, params, state,
                {k: jnp.asarray(v) for k, v in b.items()})
            losses.append(float(loss))
            if i == 0:
                grad = {k: v / (1 - opt["beta1"])
                        for k, v in flat_norms(jax.device_get(mn)).items()}
        del state
        moved = flat_norms(jax.device_get(_change(ms, params, seed)))
    return {"loss": losses, "grad": grad, "change": moved}
