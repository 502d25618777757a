"""Mamba-1 selective scan — Pallas TPU kernel.

Hardware adaptation (DESIGN.md §2): the CUDA selective-scan kernel keeps
per-thread state in registers and parallelizes over channels within an SM.
The TPU-native shape of the same insight: parallelize over (batch x channel
blocks) on the *grid*, keep the (block_d, N) state resident in VMEM across
*sequence chunks* (the innermost, sequential grid axis), and vectorize the
time-step recurrence over the channel block on the VPU.  HBM traffic is one
read of x/dt/B/C and one write of y — the state never leaves VMEM.  The
state is held transposed, ``(N, block_d)``, so channels sit on the 128
lanes and the small state dim on sublanes.

Grid: ``(B, num_channel_blocks, num_seq_chunks)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(
    x_ref,  # (chunk, block_d)
    dt_ref,  # (chunk, block_d)
    a_ref,  # (N, block_d)  A transposed: channels on lanes
    b_ref,  # (chunk, N)
    c_ref,  # (chunk, N)
    dskip_ref,  # (1, block_d)
    y_ref,  # (chunk, block_d)
    h_scr,  # (N, block_d) f32 state, carried across chunks
    x_scr,  # (chunk, block_d) f32
    dt_scr,  # (chunk, block_d) f32
    b_scr,  # (chunk, N) f32
    c_scr,  # (chunk, N) f32
    y_scr,  # (chunk, block_d) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)
    N = h_scr.shape[0]

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    # Mosaic loads single rows at a dynamic offset only from 32-bit
    # buffers, so the chunk is staged in f32 scratch once per grid step.
    x_scr[...] = x_ref[...].astype(jnp.float32)
    dt_scr[...] = dt_ref[...].astype(jnp.float32)
    b_scr[...] = b_ref[...].astype(jnp.float32)
    c_scr[...] = c_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)  # (N, block_d)
    dskip = dskip_ref[...].astype(jnp.float32)  # (1, block_d)

    def body(t, h):
        row = pl.ds(t, 1)
        xt = x_scr[row, :]  # (1, block_d)
        dtt = dt_scr[row, :]
        bt = b_scr[row, :].reshape(N, 1)
        ct = c_scr[row, :].reshape(N, 1)
        h = jnp.exp(dtt * a) * h + (dtt * xt) * bt
        y_scr[row, :] = jnp.sum(h * ct, axis=0, keepdims=True) + dskip * xt
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    y_ref[...] = y_scr[...].astype(y_ref.dtype)


def ssm_scan(
    x: jax.Array,  # (B, S, D)
    dt: jax.Array,  # (B, S, D)
    A: jax.Array,  # (D, N)
    B_in: jax.Array,  # (B, S, N)
    C_in: jax.Array,  # (B, S, N)
    D_skip: jax.Array,  # (D,)
    *,
    chunk: int = 128,
    block_d: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (B, S, D).  Zero initial state (training/prefill form)."""
    Bb, S, D = x.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    block_d = min(block_d, D)
    pad_s = (-S) % chunk
    pad_d = (-D) % block_d
    if pad_s:
        f = lambda a: jnp.pad(a, ((0, 0), (0, pad_s), (0, 0)))
        x, dt, B_in, C_in = f(x), f(dt), f(B_in), f(C_in)
    if pad_d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_d)))
        A = jnp.pad(A, ((0, pad_d), (0, 0)))
        D_skip = jnp.pad(D_skip, ((0, pad_d),))
    Sp, Dp = x.shape[1], x.shape[2]
    nd, nc = Dp // block_d, Sp // chunk

    out = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(Bb, nd, nc),
        in_specs=[
            pl.BlockSpec((None, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((None, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((N, block_d), lambda b, di, ci: (0, di)),
            pl.BlockSpec((None, chunk, N), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((1, block_d), lambda b, di, ci: (0, di)),
        ],
        out_specs=pl.BlockSpec(
            (None, chunk, block_d), lambda b, di, ci: (b, ci, di)
        ),
        out_shape=jax.ShapeDtypeStruct((Bb, Sp, Dp), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((N, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
            pltpu.VMEM((chunk, N), jnp.float32),
            pltpu.VMEM((chunk, N), jnp.float32),
            pltpu.VMEM((chunk, block_d), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, A.T, B_in, C_in, D_skip.reshape(1, Dp))
    return out[:, :S, :D]
