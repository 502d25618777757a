"""Kernel-autotuning objective: registry spaces, evaluator protocol,
sweep warm-start, and the masked-row NaN regression for the attention
kernels (interpret mode)."""

import math

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.tuning.kernel_objective import (
    HOST_KNOBS,
    KERNELS,
    KernelTuneEvaluator,
    kernel_space,
)


def test_registry_spaces_are_valid_search_spaces():
    from repro.core.space import SearchSpace

    for name, spec in KERNELS.items():
        dims = kernel_space(name)
        space = SearchSpace.from_dicts(dims)
        assert space.grid_size() >= 2, name
        # every dim name is a knob the kernel builder accepts
        assert set(space.names) <= set(spec.knobs), name


def test_kernel_space_host_knobs_are_appended():
    dims = kernel_space("rmsnorm", host_knobs=True)
    names = [d["name"] for d in dims]
    for k in HOST_KNOBS:
        assert k in names


def test_evaluator_measures_and_reports_meta():
    ev = KernelTuneEvaluator("rmsnorm", {"rows": 32, "D": 32}, iters=2)
    value, meta = ev({"block_rows": 16})
    assert math.isfinite(value) and value > 0
    assert meta["kernel"] == "rmsnorm"
    assert meta["cost_seconds"] > 0 and meta["iters"] >= 2


def test_evaluator_fidelity_contract():
    ev = KernelTuneEvaluator("gla_scan", {"B": 1, "S": 16, "H": 1,
                                          "dk": 8, "dv": 8}, iters=2)
    assert ev.supports_fidelity
    v_part, meta = ev({"chunk": 8}, fidelity=0.25)
    assert math.isfinite(v_part)
    assert meta["fidelity"] == 0.25  # partial measurements are labeled


def test_evaluator_rejects_stray_point_keys():
    ev = KernelTuneEvaluator("rmsnorm", {"rows": 16, "D": 16})
    with pytest.raises(ValueError, match="blok_rows"):
        ev({"blok_rows": 8})


def test_evaluator_rejects_host_knobs_without_subprocess():
    ev = KernelTuneEvaluator("rmsnorm", {"rows": 16, "D": 16})
    with pytest.raises(ValueError, match="allow_subprocess"):
        ev({"block_rows": 8, "host_devices": 2})


def test_host_knob_harness_refuses_when_parent_holds_tpu(monkeypatch):
    from repro.tuning import objective

    monkeypatch.setattr(objective, "parent_holds_tpu", lambda: True)
    ev = KernelTuneEvaluator("rmsnorm", {"rows": 16, "D": 16},
                             allow_subprocess=True)
    with pytest.raises(RuntimeError, match="holds the TPU"):
        ev({"block_rows": 8, "host_devices": 2})


def test_unknown_kernel_is_loud():
    with pytest.raises(ValueError, match="unknown kernel"):
        KernelTuneEvaluator("nope")


def test_sweep_cold_then_warm_measures_zero(tmp_path):
    from benchmarks.kernel_sweep import lookup_latency_ms, run_sweep
    from repro.tuning.tundb import TuningDB

    path = str(tmp_path / "tundb.json")
    kernels = ["rmsnorm", "gla_scan"]
    db = TuningDB(path)
    rows, measured = run_sweep(kernels, db, budget=2, iters=2,
                               emit=lambda *a: None)
    assert measured > 0 and len(db) == 2
    for r in rows:
        assert not r["skipped"] and math.isfinite(r["value"])
    # warm re-run from a fresh instance on the same path: 0 measurements
    warm = TuningDB(path)
    rows2, measured2 = run_sweep(kernels, warm, budget=2, iters=2,
                                 emit=lambda *a: None)
    assert measured2 == 0 and all(r["skipped"] for r in rows2)
    # the stored best round-trips verbatim
    assert [r["best"] for r in rows2] == [r["best"] for r in rows]
    assert lookup_latency_ms(warm, kernels, trials=20) < 1.0


@pytest.mark.slow
def test_subprocess_measurement_with_host_knobs():
    # host knobs need a fresh process (XLA_FLAGS is read once at jax
    # import); the harness re-invokes this module with the flags set
    import math as _math

    ev = KernelTuneEvaluator("rmsnorm", {"rows": 16, "D": 16}, iters=2,
                             allow_subprocess=True)
    v, meta = ev({"block_rows": 8, "host_devices": 2, "xla_flags": ""})
    assert _math.isfinite(v) and v > 0
    assert meta["host"]["host_devices"] == 2


# ---------------------------------------------------------------------------
# masked-row NaN regression (interpret mode vs the jnp oracle)
# ---------------------------------------------------------------------------


def _qkv(B, Sq, Sk, H, K, dh, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (B, Sq, H, dh), jnp.float32),
            jax.random.normal(kk, (B, Sk, K, dh), jnp.float32),
            jax.random.normal(kv, (B, Sk, K, dh), jnp.float32))


@pytest.mark.parametrize("case", [
    # non-causal small window with Sq > Skv: trailing query rows see no key
    dict(Sq=12, Sk=4, causal=False, window=2),
    # causal window=1, block_q padding past Sq inside the tile
    dict(Sq=5, Sk=5, causal=True, window=1),
    # causal with Sq > Skv: leading rows have an empty causal range
    dict(Sq=8, Sk=4, causal=True, window=None),
])
def test_flash_attention_masked_rows_no_nan(case):
    q, k, v = _qkv(1, case["Sq"], case["Sk"], 2, 2, 8)
    out = flash_attention(q, k, v, causal=case["causal"],
                          window=case["window"], block_q=8, block_kv=8,
                          interpret=True)
    assert not jnp.isnan(out).any(), "fully-masked rows must not emit NaN"
    expect = ref.attention_ref(q, k, v, causal=case["causal"],
                               window=case["window"])
    # compare only where the oracle itself is finite (a fully-masked row
    # is undefined in the math; the kernel pins it to exact zeros)
    alive = ~jnp.isnan(expect)
    assert jnp.allclose(jnp.where(alive, out, 0.0),
                        jnp.where(alive, expect, 0.0),
                        atol=2e-5, rtol=2e-5)
    assert (out[~alive.any(-1).any(-1)] == 0).all() if (~alive).any() else True


def test_decode_attention_length_zero_rows_no_nan():
    B, H, K, dh, Smax = 3, 2, 2, 8, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, dh), jnp.float32)
    k = jax.random.normal(kk, (B, Smax, K, dh), jnp.float32)
    v = jax.random.normal(kv, (B, Smax, K, dh), jnp.float32)
    lengths = jnp.array([0, 5, Smax], jnp.int32)  # one empty cache slot
    out = decode_attention(q, k, v, lengths, block_kv=8, interpret=True)
    assert not jnp.isnan(out).any()
    assert (out[0] == 0).all()  # length-0 row: exact zeros, not NaN
    expect = ref.decode_attention_ref(q, k, v, lengths)
    assert jnp.allclose(out[1:], expect[1:], atol=2e-5, rtol=2e-5)
