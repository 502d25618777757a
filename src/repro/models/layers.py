"""Neural layers for the model zoo (pure JAX, P-pytree params).

Every mixer implements three modes:
  * ``full``    — training forward over the whole sequence (no cache)
  * ``prefill`` — full forward that additionally materializes the decode
                  cache (KV buffers / recurrent states)
  * ``decode``  — one-token step consuming + updating the cache

Apply functions take plain value pytrees (see models/params.py) and a
``Runtime`` for backend knobs.  All matmuls run in ``rt.dtype()``;
softmax/scan statistics in fp32.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current_rules, shard_hint
from repro.kernels import ops
from repro.models.params import P, dense_init, ones_init, zeros_init
from repro.models.runtime import Runtime


def _dt(x, rt: Runtime):
    return x.astype(rt.dtype())


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int) -> dict:
    return {"scale": ones_init((d,), (None,))}


def rmsnorm(p, x, eps: float, rt: Runtime) -> jax.Array:
    return ops.rmsnorm(x, p["scale"], eps, db=rt.tuning_db)


def init_layernorm(d: int) -> dict:
    return {"scale": ones_init((d,), (None,)), "bias": zeros_init((d,), (None,))}


def layernorm(p, x, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, dh) rotate-half RoPE; positions (S,) or (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if cos.ndim == 2:  # (S, half) -> broadcast over batch
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (optionally sliding-window)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> dict:
    d, h, k_, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, dh), ("embed", "heads", "head"), fan_in=d),
        "wk": dense_init(ks[1], (d, k_, dh), ("embed", "kv_heads", "head"), fan_in=d),
        "wv": dense_init(ks[2], (d, k_, dh), ("embed", "kv_heads", "head"), fan_in=d),
        "wo": dense_init(ks[3], (h, dh, d), ("heads", "head", "embed"), fan_in=h * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((h, dh), ("heads", "head"))
        p["bk"] = zeros_init((k_, dh), ("kv_heads", "head"))
        p["bv"] = zeros_init((k_, dh), ("kv_heads", "head"))
    return p


def init_attention_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    k_, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    L = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    return {
        "k": zeros_init((batch, L, k_, dh), ("batch", "cache_seq", "kv_heads", "head"),
                        dtype=jnp.bfloat16),
        "v": zeros_init((batch, L, k_, dh), ("batch", "cache_seq", "kv_heads", "head"),
                        dtype=jnp.bfloat16),
    }


def attention_apply(
    p,
    x: jax.Array,  # (B, S, D)
    *,
    cfg: ModelConfig,
    rt: Runtime,
    mode: str,
    cache: Optional[dict] = None,
    pos: Optional[jax.Array] = None,  # scalar decode position
    use_rope: bool = True,
    causal: bool = True,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
) -> Tuple[jax.Array, Optional[dict]]:
    B, S, D = x.shape
    h, k_, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    xc = _dt(x, rt)

    q = jnp.einsum("bsd,dhk->bshk", xc, _dt(p["wq"], rt))
    if "bq" in p:
        q = q + _dt(p["bq"], rt)
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", xc, _dt(p["wk"], rt))
        v = jnp.einsum("bsd,dhk->bshk", xc, _dt(p["wv"], rt))
        if "bk" in p:
            k = k + _dt(p["bk"], rt)
            v = v + _dt(p["bv"], rt)
    else:
        k, v = kv_override

    new_cache = None
    if mode in ("full", "prefill"):
        if use_rope and kv_override is None:
            positions = jnp.arange(S)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        q = shard_hint(q, ("batch", None, "heads", None))
        out = ops.attention(
            q, k, v,
            causal=causal,
            window=cfg.sliding_window if causal else None,
            impl=rt.attn_impl,
            block_q=rt.block_q,
            block_kv=rt.block_kv,
            unroll=rt.unroll_layers,
            prune=rt.attn_prune,
            db=rt.tuning_db,
        )
        if mode == "prefill" and kv_override is None:
            new_cache = _fill_kv_cache(cfg, cache, k, v)
    else:  # decode: S == 1
        assert cache is not None and pos is not None
        if use_rope:
            posb = jnp.full((B, 1), pos)
            q = apply_rope(q, posb, cfg.rope_theta)
            k = apply_rope(k, posb, cfg.rope_theta)
        L = cache["k"].shape[1]
        slot = pos % L if cfg.sliding_window else pos
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0)
        )
        length = jnp.minimum(pos + 1, L)
        lengths = jnp.full((B,), length, jnp.int32)
        out = ops.decode_attention(
            q[:, 0], _dt(ck, rt), _dt(cv, rt), lengths,
            impl=rt.attn_impl, block_kv=rt.block_kv, db=rt.tuning_db,
        )[:, None]
        new_cache = {"k": ck, "v": cv}

    out = jnp.einsum("bshk,hkd->bsd", out, _dt(p["wo"], rt))
    return out.astype(x.dtype), new_cache


def _fill_kv_cache(cfg, cache, k, v):
    """Write prefill K/V into the cache buffer with ring alignment."""
    B, S = k.shape[0], k.shape[1]
    L = cache["k"].shape[1]
    if S >= L:
        ktail, vtail = k[:, S - L:], v[:, S - L:]
        slots = jnp.arange(S - L, S) % L
        ck = cache["k"].at[:, slots].set(ktail.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(vtail.astype(cache["v"].dtype))
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
        )
    return {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), ("embed", "lora"), fan_in=d),
        "q_norm": init_rmsnorm(m.q_lora_rank),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, h, qk_head),
                           ("lora", "heads", "head"), fan_in=m.q_lora_rank),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            ("embed", "lora"), fan_in=d),
        "kv_norm": init_rmsnorm(m.kv_lora_rank),
        "wkv_b": dense_init(
            ks[3], (m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim),
            ("lora", "heads", "head"), fan_in=m.kv_lora_rank),
        "wo": dense_init(ks[4], (h, m.v_head_dim, d), ("heads", "head", "embed"),
                         fan_in=h * m.v_head_dim),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    m = cfg.mla
    return {
        "ckv": zeros_init((batch, cache_len, m.kv_lora_rank),
                          ("batch", "cache_seq", "lora"), dtype=jnp.bfloat16),
        "krope": zeros_init((batch, cache_len, m.qk_rope_head_dim),
                            ("batch", "cache_seq", "head"), dtype=jnp.bfloat16),
    }


def mla_apply(
    p, x, *, cfg: ModelConfig, rt: Runtime, mode: str,
    cache: Optional[dict] = None, pos: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    m = cfg.mla
    B, S, D = x.shape
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = (dn + dr) ** -0.5
    xc = _dt(x, rt)

    q_lat = rmsnorm(p["q_norm"], xc @ _dt(p["wq_a"], rt), cfg.norm_eps, rt)
    q = jnp.einsum("bsr,rhk->bshk", _dt(q_lat, rt), _dt(p["wq_b"], rt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    kv_a = xc @ _dt(p["wkv_a"], rt)
    ckv = rmsnorm(p["kv_norm"], kv_a[..., : m.kv_lora_rank], cfg.norm_eps, rt)
    k_rope = kv_a[..., m.kv_lora_rank:]  # (B, S, dr) shared across heads

    if mode in ("full", "prefill"):
        positions = jnp.arange(S)
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope_r = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
        # expanded (naive) attention for the parallel modes
        kv = jnp.einsum("bsr,rhk->bshk", _dt(ckv, rt), _dt(p["wkv_b"], rt))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope_r, (*k_nope.shape[:3], dr))],
                            axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = ops.attention(
            qq, k, v, causal=True, scale=scale,
            impl=rt.attn_impl, block_q=rt.block_q, block_kv=rt.block_kv,
            unroll=rt.unroll_layers, prune=rt.attn_prune, db=rt.tuning_db,
        )
        new_cache = None
        if mode == "prefill":
            ck = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0))
            kr = jax.lax.dynamic_update_slice(
                cache["krope"], k_rope_r[:, :, 0].astype(cache["krope"].dtype),
                (0, 0, 0))
            new_cache = {"ckv": ck, "krope": kr}
    else:  # decode — absorbed latent-space attention (the point of MLA)
        posb = jnp.full((B, 1), pos)
        q_rope = apply_rope(q_rope, posb, cfg.rope_theta)
        k_rope_r = apply_rope(k_rope[:, :, None, :], posb, cfg.rope_theta)[:, :, 0]
        ck = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
        kr = jax.lax.dynamic_update_slice(
            cache["krope"], k_rope_r.astype(cache["krope"].dtype), (0, pos, 0))
        new_cache = {"ckv": ck, "krope": kr}
        wkv_b = _dt(p["wkv_b"], rt)
        w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]
        # absorb k-expansion into q: q_eff (B, H, r + dr)
        q_eff = jnp.concatenate(
            [jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], w_k), q_rope[:, 0]], axis=-1
        )
        keys = jnp.concatenate([_dt(ck, rt), _dt(kr, rt)], axis=-1)[:, :, None, :]
        vals = _dt(ck, rt)[:, :, None, :]
        lengths = jnp.full((B,), pos + 1, jnp.int32)
        o_lat = ops.decode_attention(q_eff, keys, vals, lengths, scale=scale,
                                     impl="ref")  # latent kv: ref path
        out = jnp.einsum("bhr,rhv->bhv", o_lat, w_v)[:, None]

    out = jnp.einsum("bshv,hvd->bsd", out, _dt(p["wo"], rt))
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU or GELU)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu":
        return {
            "w_up": dense_init(ks[0], (d, f), ("embed", "ff"), fan_in=d),
            "w_down": dense_init(ks[1], (f, d), ("ff", "embed"), fan_in=f),
        }
    return {
        "w_gate": dense_init(ks[0], (d, f), ("embed", "ff"), fan_in=d),
        "w_up": dense_init(ks[1], (d, f), ("embed", "ff"), fan_in=d),
        "w_down": dense_init(ks[2], (f, d), ("ff", "embed"), fan_in=f),
    }


def mlp_apply(p, x, *, cfg: ModelConfig, rt: Runtime) -> jax.Array:
    xc = _dt(x, rt)
    if "w_gate" in p:
        g = jax.nn.silu(xc @ _dt(p["w_gate"], rt))
        u = xc @ _dt(p["w_up"], rt)
        h = shard_hint(g * u, ("batch", None, "ff"))
    else:
        h = jax.nn.gelu(xc @ _dt(p["w_up"], rt))
        h = shard_hint(h, ("batch", None, "ff"))
    return (h @ _dt(p["w_down"], rt)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style grouped capacity dispatch)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), ("embed", "experts"), fan_in=d),
        "w_gate": dense_init(ks[1], (e, d, f), ("experts", "embed", "ff"), fan_in=d),
        "w_up": dense_init(ks[2], (e, d, f), ("experts", "embed", "ff"), fan_in=d),
        "w_down": dense_init(ks[3], (e, f, d), ("experts", "ff", "embed"), fan_in=f),
    }


def moe_apply(p, x, *, cfg: ModelConfig, rt: Runtime) -> Tuple[jax.Array, jax.Array]:
    """Returns (out, aux_loss).

    rt.moe_impl:
      * "gspmd" (paper-faithful baseline): grouped capacity dispatch as
        dense scatter/gather einsums; the SPMD partitioner decides the
        collectives.  Measured (EXPERIMENTS.md §Perf): it all-gathers the
        dispatch buffers across the model axis — TBs per step.
      * "ep_local" (beyond-paper): explicit expert parallelism via
        shard_map — activations are replicated across the model axis, each
        shard dispatches only to its local E/tp experts (no communication)
        and the combine is a single bf16 psum of the (B,S,D) output.
    """
    if rt.moe_impl == "ep_local" and _ep_rules_available(cfg):
        return _moe_apply_ep(p, x, cfg=cfg, rt=rt)
    return _moe_apply_gspmd(p, x, cfg=cfg, rt=rt)


def _ep_rules_available(cfg: ModelConfig) -> bool:
    rules = current_rules()
    if rules is None or "model" not in rules.mesh.axis_names:
        return False
    tp = int(rules.mesh.shape["model"])
    return cfg.moe.num_experts % tp == 0


def _moe_apply_ep(p, x, *, cfg: ModelConfig, rt: Runtime):
    """Expert-parallel MoE via shard_map (see moe_apply docstring)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as PS

    rules = current_rules()
    mesh = rules.mesh
    tp = int(mesh.shape["model"])
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    E_loc = E // tp
    cf = rt.moe_capacity_factor or m.capacity_factor
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    sharded_batch = B % rules._axis_size(batch_axes) == 0
    x_spec = PS(batch_axes if sharded_batch else None, None, None)

    def local_moe(xl, router, w_gate, w_up, w_down):
        # xl: (B_loc, S, D) — replicated across "model"; w_*: (E_loc, ...)
        Bl = xl.shape[0]
        G, T = Bl, S
        xg = _dt(xl, rt).reshape(G, T, D)
        logits = (xg @ _dt(router, rt)).astype(jnp.float32)  # full E (repl.)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        frac_probs = probs.mean(axis=(0, 1))
        assign = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
        aux = E * jnp.sum(frac_probs * assign.mean(axis=(0, 1)))

        C = max(1, int(math.ceil(cf * T * K / E)))
        oh = jax.nn.one_hot(top_i, E, dtype=jnp.int32).reshape(G, T * K, E)
        ranks = jnp.cumsum(oh, axis=1) - oh
        rank_of = jnp.sum(ranks * oh, axis=-1).reshape(G, T, K)
        keep = rank_of < C

        shard = jax.lax.axis_index("model")
        local = (top_i // E_loc) == shard  # expert lives on this shard
        e_loc = top_i % E_loc
        dump = E_loc * C
        dest = jnp.where(keep & local, e_loc * C + rank_of, dump)

        buf = jnp.zeros((G, E_loc * C + 1, D), rt.dtype())
        upd = jnp.repeat(xg, K, axis=1)  # (G, T*K, D) token per slot
        buf = buf.at[jnp.arange(G)[:, None], dest.reshape(G, T * K)].add(upd)
        buf = buf[:, : E_loc * C].reshape(G, E_loc, C, D)

        g = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, _dt(w_gate, rt)))
        u = jnp.einsum("gecd,edf->gecf", buf, _dt(w_up, rt))
        y = jnp.einsum("gecf,efd->gecd", g * u, _dt(w_down, rt))

        y_flat = y.reshape(G, E_loc * C, D)
        y_flat = jnp.concatenate([y_flat, jnp.zeros((G, 1, D), y.dtype)], 1)
        gathered = jnp.take_along_axis(
            y_flat, dest.reshape(G, T * K, 1), axis=1
        ).reshape(G, T, K, D)
        w = (top_p * (keep & local)).astype(y.dtype)
        out_local = jnp.einsum("gtkd,gtk->gtd", gathered, w)
        # single combine: bf16 psum across the expert shards
        out = jax.lax.psum(out_local, "model")
        return out.reshape(Bl, S, D), aux

    out, aux = shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(x_spec, PS(), PS("model"), PS("model"), PS("model")),
        out_specs=(x_spec, PS()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out.astype(x.dtype), aux


def _moe_apply_gspmd(
    p, x, *, cfg: ModelConfig, rt: Runtime
) -> Tuple[jax.Array, jax.Array]:
    """Paper-faithful GSPMD einsum/scatter dispatch (see moe_apply)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    cf = rt.moe_capacity_factor or m.capacity_factor

    G = rt.moe_groups or B
    T = (B * S) // G
    xg = x.reshape(G, T, D)
    xc = _dt(xg, rt)

    logits = (xc @ _dt(p["router"], rt)).astype(jnp.float32)  # (G, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)  # (G, T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch): E * mean(frac_tokens * frac_probs)
    frac_probs = probs.mean(axis=(0, 1))  # (E,)
    assign = jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32)
    frac_tokens = assign.mean(axis=(0, 1))
    aux = E * jnp.sum(frac_probs * frac_tokens)

    C = max(1, int(math.ceil(cf * T * K / E)))

    # rank of each (token, slot) within its expert, group-local
    oh = jax.nn.one_hot(top_i, E, dtype=jnp.int32)  # (G, T, K, E)
    oh_flat = oh.reshape(G, T * K, E)
    ranks = jnp.cumsum(oh_flat, axis=1) - oh_flat  # exclusive
    rank_of = jnp.sum(ranks * oh_flat, axis=-1).reshape(G, T, K)
    keep = rank_of < C

    dump = E * C  # overflow slot
    dest = jnp.where(keep, top_i * C + rank_of, dump)  # (G, T, K)

    # dispatch: scatter tokens into (G, E*C+1, D) buffers
    buf = jnp.zeros((G, E * C + 1, D), rt.dtype())
    tok_idx = jnp.broadcast_to(jnp.arange(T)[None, :, None], (G, T, K))
    upd = jnp.take_along_axis(
        xc, tok_idx.reshape(G, T * K, 1).clip(0, T - 1), axis=1
    )
    buf = buf.at[jnp.arange(G)[:, None], dest.reshape(G, T * K)].add(upd)
    buf = buf[:, : E * C].reshape(G, E, C, D)
    buf = shard_hint(buf, ("batch", "experts", None, None))

    # expert FFN (SwiGLU)
    g = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, _dt(p["w_gate"], rt)))
    u = jnp.einsum("gecd,edf->gecf", buf, _dt(p["w_up"], rt))
    h = shard_hint(g * u, ("batch", "experts", None, "ff"))
    y = jnp.einsum("gecf,efd->gecd", h, _dt(p["w_down"], rt))
    y = shard_hint(y, ("batch", "experts", None, None))

    # combine: gather each slot's output, weight, sum over k
    y_flat = y.reshape(G, E * C, D)
    y_flat = jnp.concatenate([y_flat, jnp.zeros((G, 1, D), y.dtype)], axis=1)
    gathered = jnp.take_along_axis(
        y_flat, dest.reshape(G, T * K, 1), axis=1
    ).reshape(G, T, K, D)
    w = (top_p * keep).astype(y.dtype)
    out = jnp.einsum("gtkd,gtk->gtd", gathered, w)
    return out.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Mamba-1 block (Jamba's SSM mixer)
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig) -> dict:
    mc = cfg.mamba
    d = cfg.d_model
    d_in = mc.expand * d
    dtr = mc.resolved_dt_rank(d)
    N = mc.d_state
    ks = jax.random.split(key, 6)
    # S4D-real A init: A[d, n] = -(n + 1)
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (d_in, N))
    return {
        "in_proj": dense_init(ks[0], (d, 2 * d_in), ("embed", "ff"), fan_in=d),
        "conv_w": dense_init(ks[1], (mc.d_conv, d_in), (None, "ff"), fan_in=mc.d_conv),
        "conv_b": zeros_init((d_in,), ("ff",)),
        "x_proj": dense_init(ks[2], (d_in, dtr + 2 * N), ("ff", None), fan_in=d_in),
        "dt_w": dense_init(ks[3], (dtr, d_in), (None, "ff"), fan_in=dtr),
        "dt_b": P(jnp.log(jnp.expm1(0.01 * jnp.ones(d_in))), ("ff",)),
        "A_log": P(jnp.log(A), ("ff", None)),
        "D": ones_init((d_in,), ("ff",)),
        "out_proj": dense_init(ks[4], (d_in, d), ("ff", "embed"), fan_in=d_in),
    }


def init_mamba_cache(cfg: ModelConfig, batch: int) -> dict:
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    return {
        "conv": zeros_init((batch, mc.d_conv - 1, d_in), ("batch", None, "state")),
        "h": zeros_init((batch, d_in, mc.d_state), ("batch", "state", None)),
    }


def _mamba_ssm_inputs(p, xz, cfg, rt):
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    dtr = mc.resolved_dt_rank(cfg.d_model)
    N = mc.d_state
    x, z = xz[..., :d_in], xz[..., d_in:]
    return x, z, dtr, N, d_in


def mamba_apply(
    p, x, *, cfg: ModelConfig, rt: Runtime, mode: str,
    cache: Optional[dict] = None, pos: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    mc = cfg.mamba
    B, S, D = x.shape
    xc = _dt(x, rt)
    xz = xc @ _dt(p["in_proj"], rt)  # (B, S, 2*d_in)
    xs, z, dtr, N, d_in = _mamba_ssm_inputs(p, xz, cfg, rt)

    conv_w = _dt(p["conv_w"], rt)  # (d_conv, d_in)
    if mode in ("full", "prefill"):
        pad = jnp.zeros((B, mc.d_conv - 1, d_in), xs.dtype)
        xpad = jnp.concatenate([pad, xs], axis=1)
        xconv = sum(
            xpad[:, i : i + S] * conv_w[i][None, None] for i in range(mc.d_conv)
        ) + _dt(p["conv_b"], rt)
    else:
        xprev = _dt(cache["conv"], rt)  # (B, d_conv-1, d_in)
        xpad = jnp.concatenate([xprev, xs], axis=1)  # (B, d_conv, 1? S=1)
        xconv = jnp.einsum("bcd,cd->bd", xpad, conv_w)[:, None] + _dt(p["conv_b"], rt)
    xconv = jax.nn.silu(xconv)

    xdbl = xconv @ _dt(p["x_proj"], rt)
    dt_raw, Bc, Cc = (
        xdbl[..., :dtr], xdbl[..., dtr : dtr + N], xdbl[..., dtr + N :],
    )
    dt = jax.nn.softplus(dt_raw @ _dt(p["dt_w"], rt) + _dt(p["dt_b"], rt))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    new_cache = None
    if mode == "full":
        y = ops.ssm_scan(xconv, dt, A, Bc, Cc, p["D"],
                         impl=rt.scan_impl, chunk=rt.scan_chunk,
                         db=rt.tuning_db)
    elif mode == "prefill":
        from repro.kernels.ref import ssm_scan_chunked_ref

        y, h_final = ssm_scan_chunked_ref(
            xconv, dt, A, Bc, Cc, p["D"], chunk=rt.scan_chunk
        )
        conv_state = jnp.concatenate(
            [jnp.zeros((B, mc.d_conv - 1, d_in), xs.dtype), xs], axis=1
        )[:, -(mc.d_conv - 1):]
        new_cache = {"conv": conv_state.astype(cache["conv"].dtype),
                     "h": h_final.astype(cache["h"].dtype)}
    else:  # decode: one recurrence step
        h = cache["h"].astype(jnp.float32)  # (B, d_in, N)
        dtt = dt[:, 0].astype(jnp.float32)
        xt = xconv[:, 0].astype(jnp.float32)
        Bt, Ct = Bc[:, 0].astype(jnp.float32), Cc[:, 0].astype(jnp.float32)
        h = jnp.exp(dtt[..., None] * A[None]) * h + (dtt * xt)[..., None] * Bt[:, None]
        y = (jnp.einsum("bdn,bn->bd", h, Ct)
             + xt * p["D"].astype(jnp.float32))[:, None]
        conv_state = jnp.concatenate([cache["conv"], xs.astype(cache["conv"].dtype)],
                                     axis=1)[:, 1:]
        new_cache = {"conv": conv_state, "h": h.astype(cache["h"].dtype)}

    y = _dt(y, rt) * jax.nn.silu(z)
    out = y @ _dt(p["out_proj"], rt)
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# RWKV-6 "Finch" time-mix + channel-mix
# ---------------------------------------------------------------------------


def init_rwkv_tmix(key, cfg: ModelConfig) -> dict:
    rc = cfg.rwkv
    d = cfg.d_model
    H = d // rc.head_size
    ks = jax.random.split(key, 10)
    return {
        "mu": zeros_init((5, d), (None, None)),  # static ddlerp mix for w,k,v,r,g
        "mix_w1": dense_init(ks[0], (d, 5 * rc.mix_lora), ("embed", None), fan_in=d,
                             scale=0.1),
        "mix_w2": dense_init(ks[1], (5, rc.mix_lora, d), (None, None, "embed"),
                             fan_in=rc.mix_lora, scale=0.1),
        "w_lora1": dense_init(ks[2], (d, rc.decay_lora), ("embed", None), fan_in=d,
                              scale=0.1),
        "w_lora2": dense_init(ks[3], (rc.decay_lora, d), (None, "embed"),
                              fan_in=rc.decay_lora, scale=0.1),
        "w_bias": P(jnp.full((d,), -6.0 + 5.0 * (jnp.arange(d) / max(d - 1, 1))),
                    ("embed",)),
        "wr": dense_init(ks[4], (d, d), ("embed", "heads"), fan_in=d),
        "wk": dense_init(ks[5], (d, d), ("embed", "heads"), fan_in=d),
        "wv": dense_init(ks[6], (d, d), ("embed", "heads"), fan_in=d),
        "wg": dense_init(ks[7], (d, d), ("embed", "heads"), fan_in=d),
        "wo": dense_init(ks[8], (d, d), ("heads", "embed"), fan_in=d),
        "u": dense_init(ks[9], (H, rc.head_size), ("heads", None), fan_in=1,
                        scale=0.5),
        "ln_x": init_layernorm(d),
    }


def init_rwkv_cmix(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "mu_k": zeros_init((d,), (None,)),
        "mu_r": zeros_init((d,), (None,)),
        "wk": dense_init(ks[0], (d, f), ("embed", "ff"), fan_in=d),
        "wv": dense_init(ks[1], (f, d), ("ff", "embed"), fan_in=f),
        "wr": dense_init(ks[2], (d, d), ("embed", None), fan_in=d),
    }


def init_rwkv_cache(cfg: ModelConfig, batch: int) -> dict:
    rc = cfg.rwkv
    d = cfg.d_model
    H = d // rc.head_size
    return {
        "x_tmix": zeros_init((batch, d), ("batch", None)),
        "x_cmix": zeros_init((batch, d), ("batch", None)),
        "S": zeros_init((batch, H, rc.head_size, rc.head_size),
                        ("batch", "heads", None, None)),
    }


def _token_shift(x, x_prev_last):
    """prev-token shift: returns x_{t-1} sequence.  x (B,S,D)."""
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x_prev_last is not None:
        shifted = shifted.at[:, 0].set(x_prev_last.astype(x.dtype))
    return shifted


def rwkv_tmix_apply(
    p, x, *, cfg: ModelConfig, rt: Runtime, mode: str,
    cache: Optional[dict] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    rc = cfg.rwkv
    B, S, D = x.shape
    H = D // rc.head_size
    hs = rc.head_size
    xc = _dt(x, rt)

    x_last = cache["x_tmix"] if cache is not None else None
    x_prev = _token_shift(xc, x_last)
    dx = x_prev - xc

    # data-dependent ddlerp for the five streams
    mix_base = xc + dx * _dt(p["mu"], rt)[:, None, None]  # (5, B, S, D) broadcast
    lora = jnp.tanh(xc @ _dt(p["mix_w1"], rt)).reshape(B, S, 5, rc.mix_lora)
    lora = jnp.einsum("bsfm,fmd->fbsd", lora, _dt(p["mix_w2"], rt))
    xw, xk, xv, xr, xg = [mix_base[i] + dx * lora[i] for i in range(5)]

    r = (xr @ _dt(p["wr"], rt)).reshape(B, S, H, hs)
    k = (xk @ _dt(p["wk"], rt)).reshape(B, S, H, hs)
    v = (xv @ _dt(p["wv"], rt)).reshape(B, S, H, hs)
    g = jax.nn.silu(xg @ _dt(p["wg"], rt))

    w_raw = (jnp.tanh(xw @ _dt(p["w_lora1"], rt)) @ _dt(p["w_lora2"], rt)
             + _dt(p["w_bias"], rt))
    w = jnp.exp(-jnp.exp(w_raw.astype(jnp.float32))).reshape(B, S, H, hs)
    u = p["u"].astype(jnp.float32)

    new_cache = None
    if mode == "full":
        y = ops.gla_scan(r, k, v, w.astype(r.dtype), u.astype(r.dtype),
                         impl=rt.scan_impl, chunk=rt.scan_chunk,
                         db=rt.tuning_db)
    elif mode == "prefill":
        from repro.kernels.ref import gla_scan_chunked_ref

        y, S_final = gla_scan_chunked_ref(
            r, k, v, w.astype(r.dtype), u.astype(r.dtype), chunk=rt.scan_chunk
        )
        new_cache = {"x_tmix": xc[:, -1].astype(cache["x_tmix"].dtype),
                     "S": S_final.astype(cache["S"].dtype)}
    else:  # decode: single recurrence step
        Sst = cache["S"].astype(jnp.float32)  # (B,H,hs,hs)
        rt_, kt, vt = (a[:, 0].astype(jnp.float32) for a in (r, k, v))
        wt = w[:, 0]
        bonus = jnp.einsum("bhk,hk,bhk->bh", rt_, u, kt)
        y = (jnp.einsum("bhk,bhkv->bhv", rt_, Sst)
             + bonus[..., None] * vt)[:, None]
        S_new = wt[..., None] * Sst + kt[..., None] * vt[:, :, None, :]
        new_cache = {"x_tmix": xc[:, 0].astype(cache["x_tmix"].dtype),
                     "S": S_new.astype(cache["S"].dtype)}
        y = y.astype(r.dtype)

    y = y.reshape(B, S, D)
    y = layernorm(p["ln_x"], y, 1e-5)  # per-layer output norm (rwkv ln_x)
    out = (_dt(y, rt) * g) @ _dt(p["wo"], rt)
    return out.astype(x.dtype), new_cache


def rwkv_cmix_apply(
    p, x, *, cfg: ModelConfig, rt: Runtime, mode: str,
    cache: Optional[dict] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    xc = _dt(x, rt)
    x_last = cache["x_cmix"] if cache is not None else None
    x_prev = _token_shift(xc, x_last)
    dx = x_prev - xc
    xk = xc + dx * _dt(p["mu_k"], rt)
    xr = xc + dx * _dt(p["mu_r"], rt)
    k = jnp.square(jax.nn.relu(xk @ _dt(p["wk"], rt)))
    k = shard_hint(k, ("batch", None, "ff"))
    kv = k @ _dt(p["wv"], rt)
    out = jax.nn.sigmoid(xr @ _dt(p["wr"], rt)) * kv
    new_cache = None
    if cache is not None:
        new_cache = {"x_cmix": xc[:, -1].astype(cache["x_cmix"].dtype)}
    return out.astype(x.dtype), new_cache
