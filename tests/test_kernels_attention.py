"""Pallas flash/decode attention vs pure-jnp oracles: shape/dtype sweeps
(slow), and the flash backward's gradients and structure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, ops, ref

slow = pytest.mark.slow  # Pallas kernel sweeps

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _mk(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


SWEEP = [
    # B, Sq, Sk, H, K, dh, causal, window
    (1, 16, 16, 4, 4, 16, True, None),
    (2, 37, 37, 4, 2, 16, True, None),   # GQA + ragged padding
    (1, 64, 64, 8, 1, 32, True, None),   # MQA
    (1, 50, 50, 4, 4, 16, True, 9),      # sliding window
    (2, 13, 29, 4, 1, 8, False, None),   # cross-attention shape
    (1, 128, 128, 2, 2, 64, True, None),
]


@slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", SWEEP, ids=[str(c) for c in SWEEP])
def test_flash_attention_matches_ref(rng, case, dtype):
    B, Sq, Sk, H, K, dh, causal, window = case
    q = _mk(rng, B, Sq, H, dh, dtype=dtype)
    k = _mk(rng, B, Sk, K, dh, dtype=dtype)
    v = _mk(rng, B, Sk, K, dh, dtype=dtype)
    out_ref = ops.attention(q, k, v, causal=causal, window=window, impl="ref")
    out_pal = ops.attention(q, k, v, causal=causal, window=window,
                            impl="pallas", block_q=16, block_kv=16)
    np.testing.assert_allclose(
        np.asarray(out_pal, np.float32), np.asarray(out_ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@slow
@pytest.mark.parametrize("case", SWEEP, ids=[str(c) for c in SWEEP])
def test_chunked_attention_matches_ref(rng, case):
    B, Sq, Sk, H, K, dh, causal, window = case
    q, k, v = (_mk(rng, B, Sq, H, dh), _mk(rng, B, Sk, K, dh),
               _mk(rng, B, Sk, K, dh))
    out_ref = ops.attention(q, k, v, causal=causal, window=window, impl="ref")
    for unroll in (False, True):
        out_ch = ref.attention_chunked_ref(q, k, v, causal=causal,
                                           window=window, block_q=16,
                                           unroll=unroll)
        np.testing.assert_allclose(np.asarray(out_ch), np.asarray(out_ref),
                                   atol=2e-5, rtol=2e-5)


@slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(rng, dtype):
    B, H, K, dh, Smax = 3, 8, 2, 16, 50
    q = _mk(rng, B, H, dh, dtype=dtype)
    k = _mk(rng, B, Smax, K, dh, dtype=dtype)
    v = _mk(rng, B, Smax, K, dh, dtype=dtype)
    lengths = jnp.array([50, 17, 1], jnp.int32)
    out_ref = ops.decode_attention(q, k, v, lengths, impl="ref")
    out_pal = ops.decode_attention(q, k, v, lengths, impl="pallas", block_kv=16)
    np.testing.assert_allclose(
        np.asarray(out_pal, np.float32), np.asarray(out_ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


GRAD_CASES = {
    # B, Sq, Sk, H, K, dh, dv, causal, window, dtype
    "causal-gqa2": (1, 32, 32, 4, 2, 16, 16, True, None, jnp.float32),
    "noncausal-gqa1": (1, 32, 32, 4, 4, 16, 16, False, None, jnp.float32),
    "window-9": (1, 50, 50, 4, 4, 16, 16, True, 9, jnp.float32),
    "gqa4-padded": (2, 37, 37, 4, 1, 16, 16, True, None, jnp.float32),
    "cross-sq<sk": (2, 13, 29, 4, 2, 8, 8, False, None, jnp.float32),
    "causal-sq<sk": (1, 24, 40, 4, 2, 8, 8, True, None, jnp.float32),
    "causal-sq>sk-dead-rows": (1, 40, 24, 4, 2, 8, 8, True, None, jnp.float32),
    "mla-dv<dh": (2, 33, 33, 4, 4, 24, 16, True, None, jnp.float32),
    "bf16": (1, 64, 64, 4, 2, 16, 16, True, None, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(GRAD_CASES.values()), ids=list(GRAD_CASES))
def test_attention_pallas_grads_flow(rng, case):
    """The custom_vjp pairing, Pallas forward and Pallas flash backward:
    dq, dk, dv against the reference's autodiff."""
    B, Sq, Sk, H, K, dh, dv, causal, window, dtype = case
    q, k, v = (_mk(rng, B, Sq, H, dh, dtype=dtype), _mk(rng, B, Sk, K, dh, dtype=dtype),
               _mk(rng, B, Sk, K, dv, dtype=dtype))
    w = _mk(rng, B, Sq, H, dv)

    def loss(impl):
        def f(q, k, v):
            o = ops.attention(q, k, v, causal=causal, window=window, impl=impl,
                              block_q=16, block_kv=16)
            return jnp.sum(w * o.astype(jnp.float32))
        return f

    g_pal = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss("ref"), (0, 1, 2))(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else TOL[dtype]
    for a, b in zip(g_pal, g_ref):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("causal,window,seq_q,seq_kv", [
    (True, None, 40, 40), (True, 9, 50, 50), (True, None, 24, 40),
    (True, 5, 40, 24), (False, None, 13, 29), (False, 7, 37, 37)])
def test_tile_tests_agree_with_the_mask(causal, window, seq_q, seq_kv):
    """A tile the kernels call full is wholly unmasked, one they call dead
    wholly masked; every tile of the padded grid, at two tile shapes."""
    for block_q, block_kv in ((8, 8), (16, 8)):
        band = dict(causal=causal, window=window, seq_q=seq_q, seq_kv=seq_kv,
                    block_q=block_q, block_kv=block_kv)
        for qi in range(-(-seq_q // block_q)):
            for ki in range(-(-seq_kv // block_kv)):
                mask = np.asarray(flash_attention._tile_mask(qi, ki, q_axis=0, **band))
                assert np.array_equal(
                    mask.T, flash_attention._tile_mask(qi, ki, q_axis=1, **band))
                assert bool(flash_attention._tile_full(qi, ki, **band)) == mask.all()
                if not flash_attention._tile_live(qi, ki, **band):
                    assert not mask.any()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for x in p if isinstance(p, (tuple, list)) else (p,):
                if hasattr(getattr(x, "jaxpr", x), "eqns"):
                    yield from _eqns(x)


def _kernels(jaxpr):
    """(kernel function name, number of outputs) of each pallas_call."""
    return [(e.params["jaxpr"].debug_info.func_name, len(e.outvars))
            for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]


def test_attention_pallas_backward_is_the_flash_backward(rng):
    """Differentiated, the Pallas path runs the forward with its
    log-sum-exp and the two backward kernels, and nothing holds the
    (B, H, S, S) scores; undifferentiated, it is one single-output
    kernel, the one the tune cell times."""
    B, S, H, K, dh = 1, 48, 4, 2, 16
    q, k, v = _mk(rng, B, S, H, dh), _mk(rng, B, S, K, dh), _mk(rng, B, S, K, dh)
    f = lambda q, k, v: ops.attention(q, k, v, impl="pallas", block_q=16,
                                      block_kv=16)
    assert _kernels(jax.make_jaxpr(f)(q, k, v)) == [("_flash_kernel", 1)]

    before = flash_attention.BWD_TILES[(16, 16)]
    grad = jax.make_jaxpr(jax.grad(lambda *a: f(*a).sum(), (0, 1, 2)))(q, k, v)
    assert flash_attention.BWD_TILES[(16, 16)] == before + 1
    assert sorted(_kernels(grad)) == [("_dkv_kernel", 2), ("_dq_kernel", 1),
                                      ("_flash_kernel", 2)]
    shapes = [tuple(x.aval.shape) for e in _eqns(grad) for x in e.outvars
              if hasattr(x.aval, "shape")]
    assert not [s for s in shapes if s[-2:] == (S, S)]


@slow
def test_mla_shaped_dv_differs_from_dh(rng):
    """MLA: qk head dim 96, v head dim 64 — all impls must handle it."""
    q, k = _mk(rng, 2, 33, 4, 24), _mk(rng, 2, 33, 4, 24)
    v = _mk(rng, 2, 33, 4, 16)
    o_ref = ops.attention(q, k, v, impl="ref")
    assert o_ref.shape == (2, 33, 4, 16)
    for kwargs in ({"impl": "chunked", "block_q": 16},
                   {"impl": "chunked", "block_q": 16, "unroll": True,
                    "prune": True},
                   {"impl": "pallas", "block_q": 16, "block_kv": 16}):
        o = ops.attention(q, k, v, **kwargs)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


@slow
def test_pruned_unrolled_matches_masked(rng):
    q, k, v = _mk(rng, 1, 50, 4, 16), _mk(rng, 1, 50, 2, 16), _mk(rng, 1, 50, 2, 16)
    for win in (None, 9):
        o1 = ops.attention(q, k, v, causal=True, window=win, impl="ref")
        o2 = ops.attention(q, k, v, causal=True, window=win, impl="chunked",
                           block_q=16, unroll=True, prune=True)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)


@slow
def test_fully_masked_rows_are_zero(rng):
    """Window smaller than gap -> fully masked rows must not NaN."""
    q = _mk(rng, 1, 8, 2, 8)
    k = _mk(rng, 1, 8, 2, 8)
    v = _mk(rng, 1, 8, 2, 8)
    out = ops.attention(q, k, v, causal=False, window=1, impl="pallas",
                        block_q=4, block_kv=4)
    assert not bool(jnp.isnan(out).any())
