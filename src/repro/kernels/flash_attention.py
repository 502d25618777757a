"""Flash attention, forward and backward — Pallas TPU kernels.

Tiled online-softmax attention with causal / sliding-window masking and
GQA (grouped KV heads), adapted for the TPU memory hierarchy:

* Forward grid ``(B*H, num_q_blocks, num_kv_blocks)`` — the KV dimension
  is the innermost (sequential) grid axis, so the fp32 running statistics
  (m, l, acc) live in VMEM scratch across KV steps; HBM traffic is exactly
  one read of Q/K/V and one write of O.  :func:`flash_attention_with_lse`
  also writes each row's log-sum-exp, the one residual the backward needs
  besides the inputs and the output.
* ``BlockSpec`` tiles: Q ``(block_q, head_dim)``, K/V ``(block_kv,
  head_dim)``.  ``block_q``/``block_kv`` are the backend parameters the
  paper-style tuner optimizes (the KMP_BLOCKTIME analogue — see
  DESIGN.md §2): they trade VMEM footprint against MXU utilization and
  grid overhead.
* Masking is positional (no mask tensor in HBM).  Fully-masked tiles are
  still visited but short-circuit to a no-op via ``pl.when`` on a scalar
  test of the grid position (:func:`_tile_live`).
* Every in-kernel vector is 2-D (Mosaic refuses 1-D masks and
  scratch): positions come from ``broadcasted_iota`` and the softmax
  statistics m/l live in ``(block_q, 1)`` scratch.

The backward (:func:`flash_attention_bwd`) is FlashAttention-2's: with
``D = rowsum(dO * O)`` from XLA, one kernel keeps a KV block resident and
walks the query blocks of every query head of its group on the innermost
axis, recomputing ``P = exp(s * scale - lse)`` and accumulating dK and dV;
a second keeps a query block resident and walks the KV blocks,
accumulating dQ.  The dK/dV kernel works on the transposed tile (keys on
rows, queries on lanes), so none of its products needs a transpose and
the per-row statistics arrive lane-dense.  Matmul operands are the
inputs' dtype, accumulation and softmax statistics float32.  No (S, S)
tensor reaches HBM.

Validated against ``ref.attention_ref`` in interpret mode (tests/test_kernels_attention.py).
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")

#: (block_q, block_kv) of every backward traced, by the pair chosen
BWD_TILES: collections.Counter = collections.Counter()

#: VMEM the backward's tiles may take; a v5e kernel is given 16 MiB
_BWD_VMEM_BUDGET = 12 * 2**20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


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


def _tile_full(qi, ki, *, causal, window, seq_q, seq_kv, block_q, block_kv):
    """Scalar test: is every entry of tile (qi, ki) unmasked?  Such a tile
    needs no mask (:func:`_tile_mask`)."""
    q_lo, k_lo = qi * block_q, ki * block_kv
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_kv - 1
    offset = seq_kv - seq_q
    full = (q_hi < seq_q) & (k_hi < seq_kv)
    if causal:
        full &= k_hi <= q_lo + offset
        if window is not None:
            full &= k_lo > q_hi + offset - window
    elif window is not None:
        full &= (k_hi - q_lo < window) & (q_hi - k_lo < window)
    return full


def _masked_tiles(qi, ki, band, q_axis, compute):
    """``compute(mask)`` on tile (qi, ki): with ``mask=None`` where every
    entry is live, with the tile's mask (queries along ``q_axis``) where
    some are, not at all where none is (:func:`_tile_live`)."""
    full = _tile_full(qi, ki, **band)
    pl.when(full)(lambda: compute(None))
    partial = _tile_live(qi, ki, **band) & jnp.logical_not(full)
    pl.when(partial)(lambda: compute(_tile_mask(qi, ki, q_axis=q_axis, **band)))


def _tile_mask(qi, ki, *, q_axis, causal, window, seq_q, seq_kv, block_q,
               block_kv):
    """The mask of tile (qi, ki): queries along ``q_axis`` (0 for a
    ``(block_q, block_kv)`` tile, 1 for its transpose), padding past
    ``seq_q``/``seq_kv`` masked, causal with the ``seq_kv - seq_q``
    offset, and the sliding window."""
    # positions as 2-D iotas: Mosaic lays out no 1-D masks
    shape = (block_q, block_kv) if q_axis == 0 else (block_kv, block_q)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    offset = seq_kv - seq_q  # causal alignment for Sq != Skv
    mask = (k_pos < seq_kv) & (q_pos < seq_q)
    if causal:
        mask &= k_pos <= q_pos + offset
        if window is not None:
            mask &= k_pos > q_pos + offset - window
    elif window is not None:
        mask &= jnp.abs(k_pos - q_pos) < window
    return mask


def _flash_kernel(
    q_ref,  # (block_q, dh)
    k_ref,  # (block_kv, dh)
    v_ref,  # (block_kv, dv)
    o_ref,  # (block_q, dv)
    *refs,  # [lse_ref (block_q, 1) f32], m_scr, l_scr (block_q, 1) f32, acc_scr
    scale: float,
    causal: bool,
    window: Optional[int],
    seq_q: int,
    seq_kv: int,
    block_q: int,
    block_kv: int,
):
    lse_ref = refs[0] if len(refs) == 4 else None
    m_scr, l_scr, acc_scr = refs[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    band = dict(causal=causal, window=window, seq_q=seq_q, seq_kv=seq_kv,
                block_q=block_q, block_kv=block_kv)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip tiles with no live entry (scalar test on the grid position)
    live = _tile_live(qi, ki, **band)

    @pl.when(live)
    def _compute():
        mask = _tile_mask(qi, ki, q_axis=0, **band)

        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        s = s * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_next == NEG_INF, 0.0, m_next)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)

        v = v_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, _NN, preferred_element_type=jnp.float32)
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
        if lse_ref is not None:
            # 0 on a dead row: every entry of it is masked in the backward
            lse_ref[...] = jnp.where(alive, m_scr[...] + jnp.log(denom), 0.0)


def _heads_major(x, pad):
    """(B, S, N, d) -> (B*N, S + pad, d), zero rows appended."""
    B, S, N, d = x.shape
    x = jnp.moveaxis(x, 2, 1).reshape(B * N, S, d)
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _seq_major(x, B, S):
    """(B*N, S_pad, d) -> (B, S, N, d), padding rows dropped."""
    x = x[:, :S].reshape(B, -1, S, x.shape[-1])
    return jnp.moveaxis(x, 1, 2)


def _forward(q, k, v, *, causal, window, scale, block_q, block_kv, interpret,
             with_lse):
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

    qt = _heads_major(q, pad_q)
    kt = _heads_major(k, pad_kv)
    vt = _heads_major(v, pad_kv)
    nq = qt.shape[1] // block_q
    nk = kt.shape[1] // block_kv

    def kv_index(bh, qi, ki):
        return ((bh // H) * K + (bh % H) // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window, seq_q=Sq,
        seq_kv=Sk, block_q=block_q, block_kv=block_kv)
    q_rows = lambda bh, qi, ki: (bh, qi, 0)
    out_specs = pl.BlockSpec((None, block_q, dv), q_rows)
    out_shape = jax.ShapeDtypeStruct((B * H, qt.shape[1], dv), q.dtype)
    if with_lse:
        out_specs = [out_specs, pl.BlockSpec((None, block_q, 1), q_rows)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B * H, qt.shape[1], 1), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), q_rows),
            pl.BlockSpec((None, block_kv, dh), kv_index),
            pl.BlockSpec((None, block_kv, dv), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    if not with_lse:
        return _seq_major(out, B, Sq)
    out, lse = out
    return _seq_major(out, B, Sq), _seq_major(lse, B, Sq)[..., 0]


def flash_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Attention output, (B, Sq, H, dv)."""
    return _forward(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=block_q, block_kv=block_kv, interpret=interpret,
                    with_lse=False)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
):
    """:func:`flash_attention` and each row's log-sum-exp of the scaled,
    masked scores, (B, Sq, H) float32 (0 on a fully masked row)."""
    return _forward(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=block_q, block_kv=block_kv, interpret=interpret,
                    with_lse=True)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dkv_kernel(
    q_ref,  # (block_q, dh)
    k_ref,  # (block_kv, dh)
    v_ref,  # (block_kv, dv)
    do_ref,  # (block_q, dv)
    lse_ref,  # (1, block_q) f32
    d_ref,  # (1, block_q) f32
    dk_ref,  # (block_kv, dh)
    dv_ref,  # (block_kv, dv)
    dk_scr,  # (block_kv, dh) f32
    dv_scr,  # (block_kv, dv) f32
    *,
    scale: float,
    nq: int,
    **band,
):
    """dK, dV of one KV block; the innermost axis walks (q head of the
    group, q block).  Tiles are transposed: keys on rows."""
    ki = pl.program_id(1)
    j = pl.program_id(2)
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(mask):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * scale - lse_ref[...])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - d_ref[...])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _masked_tiles(qi, ki, band, 1, _compute)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref,  # (block_q, dh)
    k_ref,  # (block_kv, dh)
    v_ref,  # (block_kv, dv)
    do_ref,  # (block_q, dv)
    lse_ref,  # (1, block_q) f32
    d_ref,  # (1, block_q) f32
    dq_ref,  # (block_q, dh)
    dq_scr,  # (block_q, dh) f32
    lse_scr,  # (block_q, 1) f32
    d_scr,  # (block_q, 1) f32
    *,
    scale: float,
    **band,
):
    """dQ of one query block; the innermost axis walks the KV blocks."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # the rows' statistics as columns, once a query block
        for row, col in ((lse_ref, lse_scr), (d_ref, d_scr)):
            wide = jnp.broadcast_to(row[...], (128, row.shape[-1]))
            col[...] = jnp.transpose(wide)[:, :1]

    def _compute(mask):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * scale - lse_scr[...])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - d_scr[...])
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _masked_tiles(qi, ki, band, 0, _compute)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _bwd_vmem(block_q: int, block_kv: int, dh: int, dv: int) -> int:
    """Bytes of VMEM the larger backward kernel takes at these tiles: six
    float32 (block_q, block_kv) intermediates, the double-buffered q-side
    and kv-side blocks and the float32 accumulators."""
    width = _lanes(dh) + _lanes(dv)
    tiles = 6 * block_q * block_kv * 4
    blocks = 2 * 4 * (block_q + block_kv) * width + 2 * 2 * 4 * _lanes(block_q)
    return tiles + blocks + 4 * block_kv * width


def _bwd_tiles(block_q: int, block_kv: int, dh: int, dv: int):
    """The call's tiles, the larger halved (to a multiple of 8) while the
    backward's VMEM (:func:`_bwd_vmem`) is over budget; never under 128."""
    half = lambda b: -(-b // 16) * 8
    while (_bwd_vmem(block_q, block_kv, dh, dv) > _BWD_VMEM_BUDGET
           and max(block_q, block_kv) > 128):
        if block_q >= block_kv:
            block_q = half(block_q)
        else:
            block_kv = half(block_kv)
    return block_q, block_kv


def flash_attention_bwd(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, K, dh)
    v: jax.Array,  # (B, Sk, K, dv)
    o: jax.Array,  # (B, Sq, H, dv)
    lse: jax.Array,  # (B, Sq, H) f32
    do: jax.Array,  # (B, Sq, H, dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
):
    """(dq, dk, dv) of :func:`flash_attention` at the cotangent ``do``,
    from the forward's output ``o`` and log-sum-exp ``lse``
    (:func:`flash_attention_with_lse`)."""
    B, Sq, H, dh = q.shape
    _, Sk, K, _ = k.shape
    dv = v.shape[-1]
    assert H % K == 0, (H, K)
    group = H // K
    scale = scale if scale is not None else dh ** -0.5

    block_q, block_kv = _bwd_tiles(min(block_q, max(Sq, 8)),
                                   min(block_kv, max(Sk, 8)), dh, dv)
    BWD_TILES[(block_q, block_kv)] += 1
    pad_q = (-Sq) % block_q
    pad_kv = (-Sk) % block_kv

    qt, dot = _heads_major(q, pad_q), _heads_major(do, pad_q)
    kt, vt = _heads_major(k, pad_kv), _heads_major(v, pad_kv)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    nq = qt.shape[1] // block_q
    nk = kt.shape[1] // block_kv
    # per-row statistics, lane-dense: (B*H*nq, 1, block_q), a row a q block
    lse_r = _heads_major(lse[..., None], pad_q).reshape(B * H * nq, 1, block_q)
    d_r = _heads_major(delta[..., None], pad_q).reshape(B * H * nq, 1, block_q)
    band = dict(causal=causal, window=window, seq_q=Sq, seq_kv=Sk,
                block_q=block_q, block_kv=block_kv)

    # dK/dV: grid (B*K, nk, group*nq); step j is q head j // nq of the
    # group, q block j % nq
    def head(bk, j):
        return (bk // K) * H + (bk % K) * group + j // nq

    q_blk = lambda bk, ki, j: (head(bk, j), j % nq, 0)
    row_blk = lambda bk, ki, j: (((bk // K) * H + (bk % K) * group) * nq + j, 0, 0)
    kv_blk = lambda bk, ki, j: (bk, ki, 0)
    dk, dvv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, nq=nq, **band),
        grid=(B * K, nk, group * nq),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), q_blk),
            pl.BlockSpec((None, block_kv, dh), kv_blk),
            pl.BlockSpec((None, block_kv, dv), kv_blk),
            pl.BlockSpec((None, block_q, dv), q_blk),
            pl.BlockSpec((None, 1, block_q), row_blk),
            pl.BlockSpec((None, 1, block_q), row_blk),
        ],
        out_specs=[pl.BlockSpec((None, block_kv, dh), kv_blk),
                   pl.BlockSpec((None, block_kv, dv), kv_blk)],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, dh), jnp.float32),
                        pltpu.VMEM((block_kv, dv), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse_r, d_r)

    # dQ: grid (B*H, nq, nk), the forward's
    q_rows = lambda bh, qi, ki: (bh, qi, 0)
    kv_rows = lambda bh, qi, ki: ((bh // H) * K + (bh % H) // group, ki, 0)
    row_rows = lambda bh, qi, ki: (bh * nq + qi, 0, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, **band),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), q_rows),
            pl.BlockSpec((None, block_kv, dh), kv_rows),
            pl.BlockSpec((None, block_kv, dv), kv_rows),
            pl.BlockSpec((None, block_q, dv), q_rows),
            pl.BlockSpec((None, 1, block_q), row_rows),
            pl.BlockSpec((None, 1, block_q), row_rows),
        ],
        out_specs=pl.BlockSpec((None, block_q, dh), q_rows),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse_r, d_r)
    return _seq_major(dq, B, Sq), _seq_major(dk, B, Sk), _seq_major(dvv, B, Sk)
