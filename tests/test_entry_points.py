"""The command-line entry points at a tiny size on the CPU: the options
that take them to the chip path, and failures that must not exit 0."""
import math
import os

import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.launch import serve, train


@pytest.fixture(autouse=True)
def _own_compile_cache(monkeypatch, tmp_path):
    # with the variable set, the entry points leave JAX's cache settings
    # of this test process alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


TRAIN = ["--arch", "qwen2-0.5b", "--reduced", "--steps", "1", "--batch", "2",
         "--seq", "32"]


@pytest.mark.parametrize("impl,dtype,rtol", [
    ("chunked", "f32", 1e-5),
    ("pallas", "f32", 1e-5),
    ("pallas", "bf16", 2e-2),
])
def test_train_main_attention_and_dtype(impl, dtype, rtol):
    want = train.main(TRAIN)[0]["loss"]  # ref attention, f32: the CPU default
    got = train.main(TRAIN + ["--attn-impl", impl, "--dtype", dtype])[0]["loss"]
    assert math.isfinite(got) and got == pytest.approx(want, rel=rtol)


SERVE = ["--arch", "qwen2-0.5b", "--requests", "3", "--batch", "2",
         "--prompt-len", "16", "--gen-len", "2"]


def test_serve_main_pallas_prefill_matches_ref():
    ref = serve.main(SERVE + ["--reduced"])
    got = serve.main(SERVE + ["--reduced", "--attn-impl", "pallas"])
    assert got["prefill_logprobs"].shape == ref["prefill_logprobs"].shape
    np.testing.assert_allclose(got["prefill_logprobs"],
                               ref["prefill_logprobs"], atol=1e-4)
    assert [rid for rid, _ in got["outputs"]] == [0, 1, 2]
    assert all(toks.shape == (2,) for _, toks in got["outputs"])


def test_serve_main_serves_the_full_config_without_reduced(monkeypatch):
    """``--reduced`` is opt-in: without it the registry's config is served
    as it is (a tiny config stands in for it here)."""
    tiny = serve.get_config("qwen2-0.5b").reduced()
    monkeypatch.setattr(serve, "get_config", lambda arch: tiny)
    monkeypatch.setattr(ModelConfig, "reduced",
                        lambda self: pytest.fail("reduced() without --reduced"))
    out = serve.main(SERVE)
    assert len(out["outputs"]) == 3


def test_tune_exits_nonzero_with_first_error_when_nothing_succeeds(monkeypatch):
    # launch/tune.py sets XLA_FLAGS when imported; keep it to this test
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    from repro.launch import tune

    class Failing:
        def __call__(self, point):
            raise RuntimeError("compiler refused the point")

    monkeypatch.setattr(tune, "RooflineEvaluator", lambda *a, **k: Failing())
    with pytest.raises(SystemExit, match="compiler refused the point"):
        tune.main(["--arch", "qwen2-0.5b", "--algo", "random", "--budget", "2"])
