"""What the program records for a profiler and for the tuner's records:
the train loop's host spans and step timer, the kernels' named scopes, and
each trial's trace/lower/compile split."""
import pathlib
import re
import threading
import time
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.kernels import ops
from repro.models import build_model
from repro.models.params import P, split_params
from repro.models.runtime import Runtime
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.train.train_step import make_train_step
from repro.train import trainer as trainer_mod
from repro.train.trainer import Trainer, TrainerConfig
from repro.tuning import evaluator
from repro.tuning.evaluator import WallClockEvaluator, compile_phases
from repro.tuning.kernel_objective import KERNELS

PHASES = ("trace_seconds", "lower_seconds", "compile_seconds")


def _stand_in_model(cfg):
    return types.SimpleNamespace(init=lambda key: {"w": P(jnp.zeros((4,)), (None,))})


def _stand_in_step(model, opt_cfg, rt, microbatches=1):
    def step(params, opt_state, batch):
        loss = jnp.mean(batch["tokens"].astype(jnp.float32)) + params["w"].sum()
        return params, opt_state, {"loss": loss, "ce": loss}

    return step


def _stand_in_trainer():
    cfg = get_config("qwen2-0.5b").reduced()
    with mock.patch.object(trainer_mod, "build_model", _stand_in_model), \
            mock.patch.object(trainer_mod, "make_train_step", _stand_in_step):
        return Trainer(cfg, OptimizerConfig(),
                       DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2),
                       TrainerConfig(steps=1, log_every=0), rt=Runtime(compute_dtype="f32"))


@pytest.fixture(scope="module")
def trainer():
    """The loop's own code, its feed included, around a stand-in model and
    step: what the step computes changes nothing in its spans or timer."""
    t = _stand_in_trainer()
    t.run()  # compiles the step
    return t


def _steps(trainer, n):
    trainer.tcfg.steps = trainer.step + n
    return trainer.run()[-n:]


# -- train loop ----------------------------------------------------------------


def test_train_loop_records_feed_and_sync_spans(trainer, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        _steps(trainer, 2)
    (path,) = pathlib.Path(tmp_path).glob("plugins/profile/*/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:")]
    names = [ev.name for p in host for line in p.lines for ev in line.events]
    assert names.count("train.feed") == 2
    assert names.count("train.sync") == 2


def test_step_seconds_cover_the_metric_sync(trainer, monkeypatch):
    """The jitted call returns at dispatch; the step's time ends after its
    metrics are read back."""

    class SlowToRead:
        def __init__(self, v):
            self.v = v

        def __float__(self):
            time.sleep(0.05)
            return float(self.v)

    real = trainer._jitted

    def step(params, opt_state, batch):
        params, opt_state, metrics = real(params, opt_state, batch)
        return params, opt_state, {"loss": SlowToRead(metrics["loss"])}

    monkeypatch.setattr(trainer, "_jitted", step)
    assert all(m["seconds"] >= 0.05 for m in _steps(trainer, 2))


def _record_batches(trainer, monkeypatch):
    """The (step, batch read back to the host) each call of the step gets."""
    seen = []
    real = trainer._jitted

    def step(params, opt_state, batch):
        seen.append((trainer.step, jax.device_get(batch)))
        return real(params, opt_state, batch)

    monkeypatch.setattr(trainer, "_jitted", step)
    return seen


def _assert_batches_are_batch_at(trainer, seen):
    for step, batch in seen:
        want = trainer.data.batch_at(step)
        assert batch.keys() == want.keys()
        assert all(np.array_equal(batch[k], want[k]) for k in want), step


def test_each_step_gets_its_batch_built_ahead(monkeypatch):
    """One ``run`` call a step, as the bench's train driver makes them: the
    first batch is built inline, every later one ahead, each bit for bit
    ``batch_at`` of its step."""
    t = _stand_in_trainer()
    monkeypatch.setattr(trainer_mod, "FEED_TOTALS", {"ahead": 0, "inline": 0})
    seen = _record_batches(t, monkeypatch)
    counts = []
    for _ in range(3):
        _steps(t, 1)
        counts.append(dict(trainer_mod.FEED_TOTALS))
    assert [s for s, _ in seen] == [0, 1, 2]
    _assert_batches_are_batch_at(t, seen)
    assert counts == [{"ahead": 0, "inline": 1}, {"ahead": 1, "inline": 1},
                      {"ahead": 2, "inline": 1}]


def test_next_batch_is_built_between_dispatch_and_read_back(monkeypatch):
    t = _stand_in_trainer()
    calls = []
    real_batch_at = t.data.batch_at

    def batch_at(step):
        calls.append(("batch_at", step))
        return real_batch_at(step)

    monkeypatch.setattr(t.data, "batch_at", batch_at)

    class LoggedRead:
        def __init__(self, step, v):
            self.step, self.v = step, v

        def __float__(self):
            calls.append(("read", self.step))
            return float(self.v)

    real = t._jitted

    def step(params, opt_state, batch):
        calls.append(("dispatch", t.step))
        params, opt_state, metrics = real(params, opt_state, batch)
        return params, opt_state, {"loss": LoggedRead(t.step, metrics["loss"])}

    monkeypatch.setattr(t, "_jitted", step)
    _steps(t, 1)
    _steps(t, 1)
    assert calls == [("batch_at", 0), ("dispatch", 0), ("batch_at", 1), ("read", 0),
                     ("dispatch", 1), ("batch_at", 2), ("read", 1)]


def test_a_rollback_feeds_the_restored_step_inline(monkeypatch):
    """``_restore`` sets the step back: the batch built ahead is for another
    step, so the restored step's is built inline."""
    t = _stand_in_trainer()
    _steps(t, 3)
    monkeypatch.setattr(trainer_mod, "FEED_TOTALS", {"ahead": 0, "inline": 0})
    seen = _record_batches(t, monkeypatch)
    t.step = 1
    _steps(t, 2)
    assert [s for s, _ in seen] == [1, 2]
    _assert_batches_are_batch_at(t, seen)
    assert trainer_mod.FEED_TOTALS == {"ahead": 1, "inline": 1}


# -- kernel scopes ---------------------------------------------------------------


def test_train_step_carries_flash_attention_scopes():
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    opt = OptimizerConfig()
    # lowered from shapes alone: the weights' values do not reach the HLO
    params = jax.eval_shape(lambda k: split_params(model.init(k))[0],
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(lambda p: adamw_init(p, opt), params)
    rt = Runtime(compute_dtype="f32", attn_impl="pallas", block_q=16, block_kv=16)
    step = make_train_step(model, opt, rt)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "targets": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    text = jax.jit(step).lower(params, opt_state, batch).as_text(debug_info=True)
    scopes = set(re.findall(r"krnl_[a-z_]+", text))
    assert {"krnl_flash_attn", "krnl_flash_attn_bwd"} <= scopes


def test_rmsnorm_pallas_branch_carries_its_scope():
    x = jnp.ones((16, 32), jnp.float32)
    scale = jnp.ones((32,), jnp.float32)

    def loss(x, scale):
        return ops.rmsnorm(x, scale, impl="pallas", block_rows=8).sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(x, scale).as_text(debug_info=True)
    assert {"krnl_rmsnorm", "krnl_rmsnorm_bwd"} <= set(re.findall(r"krnl_[a-z_]+", text))


# -- trial phase counters ----------------------------------------------------------


def _rmsnorm_evaluator():
    shape = {"rows": 16, "D": 128}
    spec = KERNELS["rmsnorm"]
    return WallClockEvaluator(lambda p: spec.build(shape, p), iters=2)


def test_trial_meta_splits_the_build():
    value, meta = _rmsnorm_evaluator()({"block_rows": 16})
    assert value > 0
    for k in PHASES:
        assert meta[k] > 0, (k, meta)
    assert sum(meta[k] for k in PHASES) <= meta["build_seconds"]


def test_a_compile_between_trials_adds_nothing():
    ev = _rmsnorm_evaluator()
    ev({"block_rows": 8})
    before = dict(evaluator.PHASE_TOTALS)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
    assert evaluator.PHASE_TOTALS == before
    _, meta = ev({"block_rows": 4})
    assert evaluator.PHASE_TOTALS["calls"] == before["calls"] + 1
    assert evaluator.PHASE_TOTALS["lower_seconds"] == pytest.approx(
        before["lower_seconds"] + meta["lower_seconds"])


def test_a_compile_on_another_thread_adds_nothing():
    def compile_elsewhere():
        jax.jit(lambda x: jnp.cos(x) + 2.0)(jnp.ones(5)).block_until_ready()

    with compile_phases() as phases:
        t = threading.Thread(target=compile_elsewhere)
        t.start()
        t.join()
    assert phases == dict.fromkeys(PHASES, 0.0)
    with compile_phases() as phases:
        compile_elsewhere()
    assert all(phases[k] > 0 for k in PHASES)


def test_nested_spans_count_once():
    spans = [(0.0, 10.0, "lower_seconds"), (2.0, 3.0, "trace_seconds"),
             (4.0, 5.0, "trace_seconds"), (10.0, 12.0, "compile_seconds"),
             (12.5, 13.0, "trace_seconds"), (12.6, 13.5, "trace_seconds")]
    assert evaluator._outermost(spans) == {
        "lower_seconds": 10.0, "compile_seconds": 2.0, "trace_seconds": 1.0}
