"""Flash-decoding attention — Pallas TPU kernel for the serve_step.

One new query token per sequence attends over a long KV cache.  Decode is
HBM-bandwidth bound (every KV byte is read once per token), so the kernel's
job is to stream KV tiles through VMEM at full bandwidth while keeping the
online-softmax statistics in scratch.

Grid ``(B*H, num_kv_blocks)``; the per-sequence valid lengths arrive by
scalar prefetch (``PrefetchScalarGridSpec``) into SMEM, so ragged batches
(continuous batching) mask correctly and KV tiles past a row's length
skip their compute.  GQA handled by index-map head folding like
flash_attention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _decode_kernel(
    len_ref,  # SMEM (B,) int32, scalar-prefetched valid cache lengths
    q_ref,  # (1, dh)
    k_ref,  # (block_kv, dh)
    v_ref,  # (block_kv, dh)
    o_ref,  # (1, dh)
    m_scr,  # (1, 1) f32
    l_scr,  # (1, 1) f32
    acc_scr,  # (1, dh) f32
    *,
    scale: float,
    block_kv: int,
    num_heads: int,
):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bh // num_heads]

    @pl.when(ki * block_kv < length)
    def _compute():
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        mask = k_pos < length
        q = q_ref[...].astype(jnp.float32)  # (1, dh)
        k = k_ref[...].astype(jnp.float32)  # (block_kv, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (1, block_kv)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_next == NEG_INF, 0.0, m_next)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        v = v_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_next

    @pl.when(ki == nk - 1)
    def _finalize():
        # length-0 rows (empty cache slots) accumulate l == 0; emit exact
        # zeros instead of 0/0 NaN
        l = l_scr[...]
        alive = l > 0.0
        denom = jnp.where(alive, l, 1.0)
        out = jnp.where(alive, acc_scr[...] / denom, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, dh)
    k: jax.Array,  # (B, Smax, K, dh)
    v: jax.Array,  # (B, Smax, K, dh)
    lengths: jax.Array,  # (B,) int32
    *,
    scale: Optional[float] = None,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, dh = q.shape
    _, Smax, K, _ = k.shape
    assert H % K == 0
    group = H // K
    scale = scale if scale is not None else dh ** -0.5

    block_kv = min(block_kv, max(Smax, 8))
    pad = (-Smax) % block_kv
    kt = jnp.moveaxis(k, 2, 1).reshape(B * K, Smax, dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * K, Smax, dh)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad), (0, 0)))
    qt = q.reshape(B * H, 1, dh)
    nk = kt.shape[1] // block_kv

    # index maps also receive the scalar-prefetch ref (unused here)
    def kv_index(bh, ki, lens):
        return ((bh // H) * K + (bh % H) // group, ki, 0)

    def row_index(bh, ki, lens):
        return (bh, 0, 0)

    kernel = functools.partial(_decode_kernel, scale=scale, block_kv=block_kv,
                               num_heads=H)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, nk),
            in_specs=[
                pl.BlockSpec((None, 1, dh), row_index),
                pl.BlockSpec((None, block_kv, dh), kv_index),
                pl.BlockSpec((None, block_kv, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((None, 1, dh), row_index),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, dh), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt)
    return out.reshape(B, H, dh)
