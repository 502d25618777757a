"""RWKV-6 wkv (gated-linear-attention) scan — Pallas TPU kernel.

State ``S (dk, dv)`` per (batch, head) stays in VMEM scratch across the
sequence chunks (innermost grid axis); the per-timestep recurrence is
vectorized over the (dk, dv) state matrix on the VPU.

    y_t = r_t @ (S + (u * k_t) ⊗ v_t)
    S  <- diag(w_t) S + k_t ⊗ v_t

Grid: ``(B*H, num_seq_chunks)``.  The chunked-quadratic (MXU/matmul) form
lives in ref.gla_scan_chunked_ref and is the documented perf iteration for
training shapes; this kernel is the exact, numerically-stable recurrence
used for decode/prefill validation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gla_kernel(
    r_ref,  # (chunk, dk)
    k_ref,  # (chunk, dk)
    v_ref,  # (chunk, dv)
    w_ref,  # (chunk, dk)
    u_ref,  # (1, dk)
    y_ref,  # (chunk, dv)
    s_scr,  # (dk, dv) f32 state, carried across chunks
    r_scr,  # (chunk, dk) f32
    k_scr,  # (chunk, dk) f32
    v_scr,  # (chunk, dv) f32
    w_scr,  # (chunk, dk) f32
    y_scr,  # (chunk, dv) f32
    *,
    chunk: int,
):
    ci = pl.program_id(1)
    dk = s_scr.shape[0]

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    # Mosaic loads single rows at a dynamic offset only from 32-bit
    # buffers, so the chunk is staged in f32 scratch once per grid step.
    r_scr[...] = r_ref[...].astype(jnp.float32)
    k_scr[...] = k_ref[...].astype(jnp.float32)
    v_scr[...] = v_ref[...].astype(jnp.float32)
    w_scr[...] = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)  # (1, dk)

    def body(t, S):
        row = pl.ds(t, 1)
        rt = r_scr[row, :]  # (1, dk)
        kt = k_scr[row, :]
        vt = v_scr[row, :]  # (1, dv)
        wt = w_scr[row, :]
        bonus = jnp.sum(rt * u * kt, axis=-1, keepdims=True)  # (1, 1)
        # r_t @ S on the VPU, in f32 like the state update: the MXU's
        # default f32 matmul rounds through bf16
        y = jnp.sum(rt.reshape(dk, 1) * S, axis=0, keepdims=True)
        y_scr[row, :] = y + bonus * vt
        return wt.reshape(dk, 1) * S + kt.reshape(dk, 1) * vt

    s_scr[...] = jax.lax.fori_loop(0, chunk, body, s_scr[...])
    y_ref[...] = y_scr[...].astype(y_ref.dtype)


def gla_scan(
    r: jax.Array,  # (B, S, H, dk)
    k: jax.Array,  # (B, S, H, dk)
    v: jax.Array,  # (B, S, H, dv)
    w: jax.Array,  # (B, S, H, dk) decay in (0, 1)
    u: jax.Array,  # (H, dk)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (B, S, H, dv).  Zero initial state."""
    B, S, H, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk

    def fold(a, d):
        a = jnp.moveaxis(a, 2, 1).reshape(B * H, S, d)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return a

    rt, kt, wt = fold(r, dk), fold(k, dk), fold(w, dk)
    vt = fold(v, dv)
    Sp = rt.shape[1]
    nc = Sp // chunk

    def u_index(bh, ci):
        return (bh % H, 0, 0)

    out = pl.pallas_call(
        functools.partial(_gla_kernel, chunk=chunk),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, chunk, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((None, 1, dk), u_index),
        ],
        out_specs=pl.BlockSpec((None, chunk, dv), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, dv), r.dtype),
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((chunk, dk), jnp.float32),
            pltpu.VMEM((chunk, dk), jnp.float32),
            pltpu.VMEM((chunk, dv), jnp.float32),
            pltpu.VMEM((chunk, dk), jnp.float32),
            pltpu.VMEM((chunk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(H, 1, dk))
    return jnp.moveaxis(out[:, :S].reshape(B, H, S, dv), 1, 2)
