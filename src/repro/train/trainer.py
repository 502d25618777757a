"""Fault-tolerant training loop.

Wires together model / optimizer / data / checkpointer / straggler
detector.  Failure handling: a ``WorkerFailure`` raised during a step
rolls back to the last checkpoint, applies an ``ElasticPlan`` (dp shrinks,
tp preserved), rebuilds the jitted step, and resumes from the restored
step — the deterministic data pipeline replays the identical stream.

Feed lookahead: once step ``s`` is dispatched, and before its metrics are
read back, the loop builds the batch of step ``s + 1`` and puts it on the
device, so the host's feed runs while the device computes. The batch is
kept keyed by its step and used when the loop reaches that step; any
other step (the first of a process, a step restored after a failure)
builds its batch inline. ``FEED_TOTALS`` counts the steps fed each way.

Under ``jax.profiler`` each built batch records a host span ``train.feed``
(built and put on the device; a lookahead's opens while the device runs
the previous step), and each step a span ``train.sync`` (the step's
metrics read back, which waits for the device).

On a mesh (``mesh`` with axes ``("data", "model")`` and a ``ShardingRules``
style), the parameters and the AdamW state are created in their shardings,
so no leaf is ever whole on one chip; the step is compiled with those
shardings and replicated metrics, each batch is placed split over
``data``, and a restore puts every leaf back in its sharding.
``STEP_COLLECTIVES`` then holds the compiled step's per-device collective
bytes, by kind. Without a mesh the loop runs on the default device.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.distributed.sharding import ShardingRules, step_shardings, under_rules
from repro.models.model import build_model
from repro.models.params import eval_shape_with_axes, split_params
from repro.models.runtime import Runtime
from repro.optim.optimizer import OptimizerConfig, adamw_init
from repro.runtime.fault_tolerance import (
    ElasticPlan,
    FailureInjector,
    StragglerDetector,
    WorkerFailure,
)
from repro.train.train_step import make_train_step
from repro.tuning.hlo_analysis import collect_collective_stats

#: process-wide count of steps by where their batch came from: ``ahead``,
#: built while the previous step ran on the device; ``inline``, built at
#: the top of the step
FEED_TOTALS = {"ahead": 0, "inline": 0}

#: per-device bytes a step of the collectives of the last step compiled on
#: a mesh, by kind (``all-gather``, ``reduce-scatter``, ...); empty until
#: a step is compiled on a mesh
STEP_COLLECTIVES: Dict[str, int] = {}


@dataclass
class TrainerConfig:
    steps: int = 100
    checkpoint_every: int = 20
    checkpoint_dir: Optional[str] = None
    microbatches: int = 1
    log_every: int = 10
    seed: int = 0


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        opt_cfg: OptimizerConfig,
        data_cfg: DataConfig,
        tcfg: TrainerConfig,
        rt: Runtime = Runtime(compute_dtype="f32"),
        failure_injector: Optional[FailureInjector] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        sharding_style: str = "fsdp_tp",
    ):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.data = SyntheticTokens(data_cfg)
        self.tcfg = tcfg
        self.rt = rt
        self.model = build_model(cfg)
        self.failures = failure_injector
        self.straggler = StragglerDetector()
        self.ckpt = (Checkpointer(tcfg.checkpoint_dir)
                     if tcfg.checkpoint_dir else None)
        self.metrics_log: List[Dict] = []
        self.events: List[str] = []

        key = jax.random.PRNGKey(tcfg.seed)
        self.rules = self.shardings = None
        if mesh is None:
            params_tree = self.model.init(key)
            self.params, self.params_axes = split_params(params_tree)
            self.opt_state = adamw_init(self.params, opt_cfg)
        else:
            self._init_sharded(ShardingRules(mesh, sharding_style), key)
        self._build_step()
        self.step = 0
        #: (step, batch on the device) built ahead by the previous step
        self._ahead = None

    def _init_sharded(self, rules: ShardingRules, key):
        """Parameters and AdamW state made by jitted inits whose outputs are
        already split over the mesh."""
        struct, self.params_axes = eval_shape_with_axes(lambda: self.model.init(key))
        d = self.data.cfg
        self._batch_specs = self.model.input_specs(
            ShapeConfig("train", d.seq_len, d.global_batch, "train"))
        self.rules = rules
        self.shardings = sh = step_shardings(rules, self.params_axes, struct,
                                             self._batch_specs, self.opt_cfg)
        # the key is an argument, so every seed runs one compiled init
        self.params = jax.jit(lambda k: split_params(self.model.init(k))[0],
                              out_shardings=sh.params)(key)
        self.opt_state = jax.jit(lambda p: adamw_init(p, self.opt_cfg),
                                 out_shardings=sh.opt)(self.params)

    def _build_step(self):
        step_fn = make_train_step(self.model, self.opt_cfg, self.rt,
                                  microbatches=self.tcfg.microbatches)
        if self.shardings is None:
            self._jitted = jax.jit(step_fn, donate_argnums=(0, 1))
            return
        sh = self.shardings
        jitted = jax.jit(under_rules(step_fn, self.rules),
                         in_shardings=(sh.params, sh.opt, sh.batch),
                         out_shardings=(sh.params, sh.opt, sh.metrics),
                         donate_argnums=(0, 1))
        batch = {k: jax.ShapeDtypeStruct(v.struct.shape, v.struct.dtype,
                                         sharding=sh.batch[k])
                 for k, v in self._batch_specs.items()}
        self._jitted = jitted.lower(self.params, self.opt_state, batch).compile()
        STEP_COLLECTIVES.clear()
        STEP_COLLECTIVES.update(
            collect_collective_stats(self._jitted.as_text()).bytes_by_kind)

    # -- checkpoint/restart ----------------------------------------------------
    def _save(self, metric: Optional[float] = None):
        if not self.ckpt:
            return
        self.ckpt.save(
            self.step,
            {"params": self.params, "opt": self.opt_state},
            metadata={"config": self.cfg.name},
            metric=metric,
        )

    def _restore(self):
        assert self.ckpt is not None, "failure without checkpointing enabled"
        self.ckpt.wait()  # a save still being written is the one to restore
        like = {"params": self.params, "opt": self.opt_state}
        sh = self.shardings
        restored, meta = self.ckpt.restore(
            None, like, shardings=None if sh is None else
            {"params": sh.params, "opt": sh.opt})
        self.params, self.opt_state = restored["params"], restored["opt"]
        self.step = int(meta["step"])
        self.events.append(f"restored step {self.step}")

    # -- main loop ---------------------------------------------------------------
    def _feed(self, step: int) -> Dict:
        with jax.profiler.TraceAnnotation("train.feed"):
            batch_np = self.data.batch_at(step)
            if self.shardings is not None:
                return jax.device_put(batch_np, self.shardings.batch)
            return {k: jax.numpy.asarray(v) for k, v in batch_np.items()}

    def run(self) -> List[Dict]:
        last_metric = None
        if self.ckpt and self.ckpt.latest_step() is not None:
            self._restore()
        while self.step < self.tcfg.steps:
            ahead, self._ahead = self._ahead, None
            if ahead is not None and ahead[0] == self.step:
                batch = ahead[1]
                FEED_TOTALS["ahead"] += 1
            else:
                batch = self._feed(self.step)
                FEED_TOTALS["inline"] += 1
            t0 = time.perf_counter()
            try:
                if self.failures is not None:
                    self.failures.check(self.step)
                self.params, self.opt_state, metrics = self._jitted(
                    self.params, self.opt_state, batch
                )
            except WorkerFailure as e:
                self.events.append(f"failure at step {e.step}")
                plan = ElasticPlan.after_failure(dp=2, tp=1,
                                                 lost_chips=e.failed_workers)
                self.events.append(
                    f"elastic rescale dp {plan.old_dp}->{plan.new_dp}"
                )
                self._restore()
                self._build_step()  # re-jit for the (new) topology
                continue
            # the dispatch above returns at once: the next step's batch is
            # built while the device runs this one
            self._ahead = (self.step + 1, self._feed(self.step + 1))
            with jax.profiler.TraceAnnotation("train.sync"):
                metrics = {k: float(v) for k, v in metrics.items()}
            # the step's wall time: the jitted call returns at dispatch, the
            # sync above waits for the device
            dt = time.perf_counter() - t0
            if self.straggler.update(dt):
                self.events.append(f"straggler flagged at step {self.step}")
            metrics.update(step=self.step, seconds=dt)
            self.metrics_log.append(metrics)
            last_metric = -metrics["loss"]
            if self.tcfg.log_every and self.step % self.tcfg.log_every == 0:
                print(f"[train] step {self.step:5d} loss {metrics['loss']:.4f} "
                      f"({dt*1e3:.0f} ms)")
            self.step += 1
            if self.ckpt and self.step % self.tcfg.checkpoint_every == 0:
                self._save(metric=last_metric)
        if self.ckpt:
            self._save(metric=last_metric)
            self.ckpt.wait()
        return self.metrics_log
