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


@pytest.fixture(scope="module")
def trainer():
    """The loop's own code, its feed included, around a stand-in model and
    step: what the step computes changes nothing in its spans or timer."""
    cfg = get_config("qwen2-0.5b").reduced()
    with mock.patch.object(trainer_mod, "build_model", _stand_in_model), \
            mock.patch.object(trainer_mod, "make_train_step", _stand_in_step):
        t = Trainer(cfg, OptimizerConfig(),
                    DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2),
                    TrainerConfig(steps=1, log_every=0), rt=Runtime(compute_dtype="f32"))
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
