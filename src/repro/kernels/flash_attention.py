"""Flash attention forward — Pallas TPU kernel.

Tiled online-softmax attention with causal / sliding-window masking and
GQA (grouped KV heads), adapted for the TPU memory hierarchy:

* Grid ``(B*H, num_q_blocks, num_kv_blocks)`` — the KV dimension is the
  innermost (sequential) grid axis, so the fp32 running statistics
  (m, l, acc) live in VMEM scratch across KV steps; HBM traffic is exactly
  one read of Q/K/V and one write of O.
* ``BlockSpec`` tiles: Q ``(block_q, head_dim)``, K/V ``(block_kv,
  head_dim)``.  ``block_q``/``block_kv`` are the backend parameters the
  paper-style tuner optimizes (the KMP_BLOCKTIME analogue — see
  DESIGN.md §2): they trade VMEM footprint against MXU utilization and
  grid overhead.
* Masking is positional (no mask tensor in HBM).  Fully-masked KV tiles
  are still visited but short-circuit to a no-op via ``pl.when`` on a
  scalar test of the grid position — tile *pruning* for the causal
  lower-triangle is a documented perf iteration (EXPERIMENTS.md §Perf).
* Every in-kernel vector is 2-D (Mosaic refuses 1-D masks and
  scratch): positions come from ``broadcasted_iota`` and the softmax
  statistics m/l live in ``(block_q, 1)`` scratch.

Validated against ``ref.attention_ref`` in interpret mode (tests/test_kernels_attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _tile_live(qi, ki, *, causal, window, seq_q, seq_kv, block_q, block_kv):
    """Scalar test: does tile (qi, ki) hold at least one unmasked entry?

    The mask is a band in (q, k), so the keys reachable from the tile's
    query rows form one interval; the tile is live iff that interval
    meets the tile's key columns.  Conservative in the only direction
    that matters: a live tile is never reported dead."""
    q_lo = qi * block_q
    q_hi = jnp.minimum(q_lo + block_q, seq_q) - 1
    k_lo = ki * block_kv
    k_hi = jnp.minimum(k_lo + block_kv, seq_kv) - 1
    offset = seq_kv - seq_q
    if causal:
        hi = jnp.minimum(k_hi, q_hi + offset)
        lo = k_lo if window is None else jnp.maximum(
            k_lo, q_lo + offset - window + 1)
    elif window is not None:
        hi = jnp.minimum(k_hi, q_hi + window - 1)
        lo = jnp.maximum(k_lo, q_lo - window + 1)
    else:
        hi, lo = k_hi, k_lo
    return lo <= hi


def _flash_kernel(
    q_ref,  # (block_q, dh)
    k_ref,  # (block_kv, dh)
    v_ref,  # (block_kv, dh)
    o_ref,  # (block_q, dh)
    m_scr,  # (block_q, 1) f32
    l_scr,  # (block_q, 1) f32
    acc_scr,  # (block_q, dh) f32
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    seq_q: int,
    seq_kv: int,
    block_q: int,
    block_kv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip tiles with no live entry (scalar test on the grid position)
    live = _tile_live(qi, ki, causal=causal, window=window, seq_q=seq_q,
                      seq_kv=seq_kv, block_q=block_q, block_kv=block_kv)

    @pl.when(live)
    def _compute():
        # positions as 2-D iotas: Mosaic lays out no 1-D masks
        shape = (block_q, block_kv)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        offset = seq_kv - seq_q  # causal alignment for Sq != Skv
        mask = (k_pos < seq_kv) & (q_pos < seq_q)
        if causal:
            mask &= k_pos <= q_pos + offset
            if window is not None:
                mask &= k_pos > q_pos + offset - window
        elif window is not None:
            mask &= jnp.abs(k_pos - q_pos) < window

        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_next == NEG_INF, 0.0, m_next)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)

        v = v_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_next

    @pl.when(ki == nk - 1)
    def _finalize():
        # A fully-masked query row (padding past seq_q, or a small window
        # with nothing in range) accumulates l == 0; emit exact zeros for
        # it instead of 0/0 NaN.
        l = l_scr[...]
        alive = l > 0.0
        denom = jnp.where(alive, l, 1.0)
        out = jnp.where(alive, acc_scr[...] / denom, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, dh = q.shape
    _, Sk, K, _ = k.shape
    dv = v.shape[-1]
    assert H % K == 0, (H, K)
    group = H // K
    scale = scale if scale is not None else dh ** -0.5

    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Sk, 8))
    pad_q = (-Sq) % block_q
    pad_kv = (-Sk) % block_kv

    qt = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * K, Sk, dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * K, Sk, dv)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_kv), (0, 0)))

    nq = qt.shape[1] // block_q
    nk = kt.shape[1] // block_kv

    def kv_index(bh, qi, ki):
        return ((bh // H) * K + (bh % H) // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        seq_q=Sq,
        seq_kv=Sk,
        block_q=block_q,
        block_kv=block_kv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_kv, dh), kv_index),
            pl.BlockSpec((None, block_kv, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, dv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, qt.shape[1], dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :Sq].reshape(B, H, Sq, dv)
    return jnp.moveaxis(out, 1, 2)
