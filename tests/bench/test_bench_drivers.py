"""Whole runs of the train and tune cells at a tiny size on the CPU, past
the harness's look for a chip: the result's shape, and ``correct`` false
when the timed path is broken underneath."""
import functools
import math
import time
from unittest import mock

import jax
import pytest

from bench import harness
from bench_fixtures import tiny_root, tiny_tune_root

TRAIN = "qwen2-0.5b.train-2x1024"
TUNE = "qwen2-0.5b.tune-attn"


def _run(root, cell, seconds=0.5, trace=False):
    return harness.run(cell, 2**31 + 9, seconds, trace, t_start=time.perf_counter(),
                       require_chip=False, root=root, log=lambda s: None)


@pytest.fixture(scope="module")
def train_root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("train"))


@pytest.fixture(scope="module")
def tune_root(tmp_path_factory):
    return tiny_tune_root(tmp_path_factory.mktemp("tune"))


def test_train_run(train_root):
    r = _run(train_root, TRAIN)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "compared"
    assert set(r["compared"]) == {"loss_gap", "grad_gap", "change_gap"}


def _broken_step(how):
    from repro.train import train_step

    real = train_step.make_train_step

    def make(*a, **k):
        step = real(*a, **k)

        def broken(params, opt_state, batch):
            if how == "unchanged":
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics
            half = {n: v[: v.shape[0] // 2] for n, v in batch.items()}
            return step(params, opt_state, half)

        return broken

    return make


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_train_faults_are_not_correct(train_root, how):
    with mock.patch("repro.train.trainer.make_train_step", _broken_step(how)):
        r = _run(train_root, TRAIN)
    assert r["correct"] is False, r["compared"]


def test_train_wrong_global_norm_is_not_correct(train_root):
    """A clip factor from a wrong global norm barely moves the loss or the
    change under Adam; the clipped first gradient shows it."""
    from repro.optim import optimizer

    real = optimizer.global_norm
    with mock.patch.object(optimizer, "global_norm", lambda t: 2.0 * real(t)):
        r = _run(train_root, TRAIN)
    assert r["compared"]["grad_gap"]["value"] > r["compared"]["grad_gap"]["limit"]
    assert r["correct"] is False


def test_a_padded_vocabulary_is_refused(train_root):
    cell = harness.resolve(TRAIN, 5, train_root)
    train = harness.load_driver("train", train_root / "bench")
    with pytest.raises(ValueError):
        train.model_config(dict(cell.config, vocab_size=500))


def test_tune_run(tune_root):
    r = _run(tune_root, TUNE, seconds=4.0)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {"tune_trials_per_s", "tuned_speedup", "setup_s"}
    assert set(r["compared"]) == {"attn_err", "attn_rms", "tile_mismatch"}


def test_tune_altered_answer_is_not_correct(tune_root):
    from repro.kernels import ops

    real = ops.attention

    @functools.wraps(real)
    def altered(*a, **k):
        return real(*a, **k).at[0, 0, 0, 0].add(1.0)

    with mock.patch.object(ops, "attention", altered):
        r = _run(tune_root, TUNE, seconds=3.0)
    assert r["compared"]["attn_err"]["value"] > r["compared"]["attn_err"]["limit"]
    assert r["correct"] is False


def test_tune_bf16_attention_is_not_correct(tune_root):
    """The control: attention computed wholly in bfloat16 in the kernel's
    place, through a whole run."""
    from bench import reference
    from repro.kernels import ops

    def bf16(q, k, v, **_):
        return reference.attention(q, k, v, dot=reference.make_dot("bf16")
                                   ).astype(q.dtype)

    with mock.patch.object(ops, "attention", bf16):
        r = _run(tune_root, TUNE, seconds=3.0)
    assert any(c["value"] > c["limit"] for c in r["compared"].values()), r["compared"]
    assert r["correct"] is False


def test_tune_recorded_tile_must_be_the_chosen_one(tune_root):
    from repro.tuning.tundb import TuningDB

    real = TuningDB.record

    def record(self, kernel, dims, config, value, **k):
        return real(self, kernel, dims, dict(config, block_q=8), value, **k)

    with mock.patch.object(TuningDB, "record", record):
        r = _run(tune_root, TUNE, seconds=3.0)
    assert r["correct"] is False


def test_a_cache_hit_fails_its_trial(tune_root):
    from jax._src import monitoring
    from repro.tuning.kernel_objective import KernelTuneEvaluator

    real = KernelTuneEvaluator.__call__

    def hit(self, point, fidelity=None):
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        return real(self, point, fidelity=fidelity)

    cell = harness.resolve(TUNE, 3, tune_root)
    driver = harness.load_driver("tune", tune_root / "bench").Driver(cell)
    driver.setup()
    with mock.patch.object(KernelTuneEvaluator, "__call__", hit):
        driver.window(2.0, harness.Tracer(False, 1, tune_root / "tr"))
    c = driver.counters
    assert c["attempted"] > 0 and c["failed"] == c["attempted"]
    assert c["cache_hits"] >= c["attempted"]
    assert driver.end_to_end()["tune_trials_per_s"] == 0
    assert jax.config.jax_enable_compilation_cache
    assert not math.isnan(c["window_s"])
