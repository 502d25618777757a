"""Public kernel API: jit'd wrappers with implementation dispatch.

``impl``:
  * ``"ref"``    — pure-jnp oracle (differentiable; used on CPU and for the
                   dry-run lowering).
  * ``"pallas"`` — the Pallas TPU kernel, compiled by Mosaic on a TPU.  On
                   the CPU backend it runs in interpret mode (correctness
                   validation); any other backend is an error.
  * ``"chunked"``— matmul-friendly chunked jnp form (scans only).

Pallas forward passes get a ``jax.custom_vjp`` whose backward recomputes
through the reference implementation — the standard remat-style pairing
that keeps the training graph differentiable while the fwd hot-spot runs
the hand-written kernel.

A Mosaic kernel cannot be partitioned by the compiler, so when the active
sharding rules' mesh has more than one device the flash forward runs per
shard under ``shard_map``: batch over its mesh axis, query and KV heads
over ``model``.  Its backward, plain ``jnp``, is partitioned as usual.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec

from repro.distributed.sharding import current_rules
from repro.kernels import decode_attention as _decode_mod
from repro.kernels import flash_attention as _flash_mod
from repro.kernels import gla_scan as _gla_mod
from repro.kernels import rmsnorm as _rms_mod
from repro.kernels import ssm_scan as _ssm_mod
from repro.kernels import ref

_VALID_IMPLS = ("ref", "pallas", "chunked")


def _interpret() -> bool:
    """Interpret mode on the CPU, the compiled kernel on a TPU.  Any other
    backend raises: silently interpreting there would hide the device."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for a TPU or run interpreted on the "
            f"CPU; the default JAX backend is {backend!r}")
    return backend == "cpu"


def _tuned(db, kernel: str, dims: dict, defaults: dict) -> dict:
    """Trace-time TuningDB consult: best-known tile config for this
    kernel at these call shapes, else the caller's heuristic defaults.

    Runs while the wrapper is being traced (shapes are concrete python
    ints), so a hit rewrites the tile knobs of the jaxpr being built and
    costs nothing per step.  ``db=None`` — the default everywhere — is
    byte-identical to the historical behavior.
    """
    if db is None:
        return defaults
    cfg = db.kernel_config(kernel, dims)
    if not cfg:
        return defaults
    return {k: int(cfg.get(k, v)) for k, v in defaults.items()}


def _ref_vjp(pallas_fn, ref_fn, scope: str):
    """custom_vjp: pallas forward, reference-recompute backward.  The
    forward runs under the named scope ``scope``, the backward under
    ``scope + "_bwd"``, so a trace names the kernel's device time."""

    @jax.custom_vjp
    def fn(*args):
        with jax.named_scope(scope):
            return pallas_fn(*args)

    def fwd(*args):
        with jax.named_scope(scope):
            return pallas_fn(*args), args

    def bwd(args, g):
        with jax.named_scope(scope + "_bwd"):
            _, vjp = jax.vjp(ref_fn, *args)
            return vjp(g)

    fn.defvjp(fwd, bwd)
    return fn


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _per_shard(fn, rules, q_shape, kv_shape):
    """``fn(q, k, v)`` under ``shard_map`` on the rules' mesh: batch over
    its axis, q heads and kv heads over ``model``.  Heads are split only
    where both counts divide, so each shard's q heads keep their own KV
    heads (GQA's grouping is unchanged)."""
    from jax import shard_map

    def spec(logical, shape):
        s = tuple(rules.spec_for(logical, shape))
        return s + (None,) * (4 - len(s))

    q_spec = spec(("batch", None, "heads", None), q_shape)
    kv_spec = spec(("batch", None, "kv_heads", None), kv_shape)
    if q_spec[2] != kv_spec[2]:
        q_spec = kv_spec = spec(("batch",), q_shape[:1])
    q_spec, kv_spec = PartitionSpec(*q_spec), PartitionSpec(*kv_spec)
    return shard_map(fn, mesh=rules.mesh, in_specs=(q_spec, kv_spec, kv_spec),
                     out_specs=q_spec, check_vma=False)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "ref",
    block_q: int = 128,
    block_kv: int = 128,
    unroll: bool = False,
    prune: bool = False,
    db=None,
) -> jax.Array:
    """(B,Sq,H,dh) x (B,Sk,K,dh) -> (B,Sq,H,dh)."""
    assert impl in _VALID_IMPLS, impl
    if impl == "ref":
        return ref.attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    B, Sq, H, dh = q.shape
    _, Sk, K, _ = k.shape
    t = _tuned(db, "flash_attention",
               {"B": B, "Sq": Sq, "Sk": Sk, "H": H, "K": K, "dh": dh},
               {"block_q": block_q, "block_kv": block_kv})
    block_q, block_kv = t["block_q"], t["block_kv"]
    if impl == "chunked":
        with jax.named_scope("krnl_flash_attn"):
            return ref.attention_chunked_ref(
                q, k, v, causal=causal, window=window, scale=scale,
                block_q=block_q, unroll=unroll, prune=prune,
            )

    pallas_fn = functools.partial(
        _flash_mod.flash_attention,
        causal=causal,
        window=window,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        interpret=_interpret(),
    )
    rules = current_rules()
    if rules is not None and rules.mesh.size > 1:
        pallas_fn = _per_shard(pallas_fn, rules, q.shape, k.shape)
    ref_fn = functools.partial(
        ref.attention_ref, causal=causal, window=window, scale=scale
    )
    return _ref_vjp(pallas_fn, ref_fn, "krnl_flash_attn")(q, k, v)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    impl: str = "ref",
    block_kv: int = 512,
    db=None,
) -> jax.Array:
    """(B,H,dh) x (B,Smax,K,dh) cache + (B,) lengths -> (B,H,dh)."""
    assert impl in _VALID_IMPLS, impl
    if impl in ("ref", "chunked"):
        with jax.named_scope("krnl_decode_attn"):
            return ref.decode_attention_ref(q, k, v, lengths, scale=scale)
    B, H, dh = q.shape
    _, Smax, K, _ = k.shape
    block_kv = _tuned(db, "decode_attention",
                      {"B": B, "H": H, "K": K, "dh": dh, "Smax": Smax},
                      {"block_kv": block_kv})["block_kv"]
    return _decode_mod.decode_attention(
        q, k, v, lengths, scale=scale, block_kv=block_kv, interpret=_interpret()
    )


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(
    x: jax.Array,
    scale: jax.Array,
    eps: float = 1e-5,
    *,
    impl: str = "ref",
    block_rows: int = 256,
    db=None,
) -> jax.Array:
    assert impl in _VALID_IMPLS, impl
    if impl in ("ref", "chunked"):
        return ref.rmsnorm_ref(x, scale, eps)
    rows = 1
    for d in x.shape[:-1]:
        rows *= int(d)
    block_rows = _tuned(db, "rmsnorm", {"rows": rows, "D": x.shape[-1]},
                        {"block_rows": block_rows})["block_rows"]
    pallas_fn = functools.partial(
        _rms_mod.rmsnorm, eps=eps, block_rows=block_rows, interpret=_interpret()
    )
    ref_fn = functools.partial(ref.rmsnorm_ref, eps=eps)
    return _ref_vjp(pallas_fn, ref_fn, "krnl_rmsnorm")(x, scale)


# ---------------------------------------------------------------------------
# Scans
# ---------------------------------------------------------------------------


def ssm_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B_in: jax.Array,
    C_in: jax.Array,
    D_skip: jax.Array,
    *,
    impl: str = "chunked",
    chunk: int = 128,
    block_d: int = 256,
    db=None,
) -> jax.Array:
    """Selective scan, zero init state.  Returns y (B,S,D)."""
    assert impl in _VALID_IMPLS, impl
    if impl == "ref":
        return ref.ssm_scan_ref(x, dt, A, B_in, C_in, D_skip)[0]
    B, S, D = x.shape
    t = _tuned(db, "ssm_scan",
               {"B": B, "S": S, "D": D, "N": A.shape[-1]},
               {"chunk": chunk, "block_d": block_d})
    chunk, block_d = t["chunk"], t["block_d"]
    if impl == "chunked":
        with jax.named_scope("krnl_ssm_scan"):
            return ref.ssm_scan_chunked_ref(
                x, dt, A, B_in, C_in, D_skip, chunk=chunk
            )[0]
    pallas_fn = functools.partial(
        _ssm_mod.ssm_scan, chunk=chunk, block_d=block_d, interpret=_interpret()
    )
    ref_fn = lambda *a: ref.ssm_scan_chunked_ref(*a, chunk=chunk)[0]
    return _ref_vjp(pallas_fn, ref_fn, "krnl_ssm_scan")(x, dt, A, B_in, C_in, D_skip)


def gla_scan(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    impl: str = "chunked",
    chunk: int = 64,
    db=None,
) -> jax.Array:
    """RWKV-6 wkv scan, zero init state.  Returns y (B,S,H,dv)."""
    assert impl in _VALID_IMPLS, impl
    if impl == "ref":
        return ref.gla_scan_ref(r, k, v, w, u)[0]
    B, S, H, dk = k.shape
    chunk = _tuned(db, "gla_scan",
                   {"B": B, "S": S, "H": H, "dk": dk, "dv": v.shape[-1]},
                   {"chunk": chunk})["chunk"]
    if impl == "chunked":
        with jax.named_scope("krnl_gla_scan"):
            return ref.gla_scan_chunked_ref(r, k, v, w, u, chunk=chunk)[0]
    pallas_fn = functools.partial(
        _gla_mod.gla_scan, chunk=chunk, interpret=_interpret()
    )
    ref_fn = lambda *a: ref.gla_scan_chunked_ref(*a, chunk=chunk)[0]
    return _ref_vjp(pallas_fn, ref_fn, "krnl_gla_scan")(r, k, v, w, u)
