"""Train driver: the program's own training loop, ``Trainer.run``.

Set-up builds one ``Trainer`` from the configuration and traffic files and
drives it from the seed through the first ``CHECK_STEPS`` steps, through the
same ``run`` call and synthetic feed the window uses. Those steps compile
the step program and give the readings the check compares with the
reference: each step's loss, the first gradient as the optimizer applied
it, clipped (its first moment after one step over 1 - beta1), and the
parameters' change. The
window then calls ``run`` one step at a time until ``--seconds`` have passed;
each step ends with the loop's own sync on its metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp

from bench import reference

#: ModelConfig fields set from the configuration file
_FIELDS = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
           "vocab_size": "vocab_size", "head_dim": "head_dim",
           "qkv_bias": "attention_bias", "sliding_window": "sliding_window",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "tie_embeddings": "tie_word_embeddings",
           "vocab_pad_multiple": "vocab_pad_multiple"}

#: a leaf whose reference gradient is under this share of the median leaf's
#: moves under Adam by round-off alone; its change is not compared
_STILL = 1e-3
#: steps of set-up whose readings the check compares with the reference
CHECK_STEPS = 3
#: the share of the window the traced run profiles, from its start
TRACE_SECONDS = 3


def model_config(model: dict):
    """The program's ModelConfig for the configuration file's numbers."""
    from repro.configs import get_config

    base = get_config(model["program_arch"])
    cfg = dataclasses.replace(base, **{f: model[k] for f, k in _FIELDS.items()})
    if (cfg.resolved_head_dim != model["head_dim"] or cfg.act != model["hidden_act"]
            or cfg.padded_vocab != model["vocab_size"]):
        raise ValueError(f"program config {cfg} does not match the file")
    return cfg


def worst_gap(prog: dict, ref: dict, keep=None):
    """Worst leaf of |prog - ref| / max(ref, median ref), over ``keep``."""
    names = sorted(keep if keep is not None else ref)
    med = statistics.median(ref[n] for n in names)
    worst, where = 0.0, None
    for n in names:
        p = prog.get(n, math.nan)
        gap = abs(p - ref[n]) / max(ref[n], med) if math.isfinite(p) else math.inf
        if not gap <= worst:
            worst, where = gap, n
    return worst, where


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers a train cell compares, from readings shaped like
    :func:`bench.reference.lm_readings`'s."""
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["loss"], ref["loss"]))
    grad, grad_at = worst_gap(prog["grad"], ref["grad"])
    med = statistics.median(ref["grad"].values())
    moving = [n for n, g in ref["grad"].items() if g >= _STILL * med]
    change, change_at = worst_gap(prog["change"], ref["change"], moving)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "_where": {"grad_gap": grad_at, "change_gap": change_at,
                       "still_leaves": len(ref["grad"]) - len(moving)}}


class Driver:
    trace_seconds = TRACE_SECONDS

    def __init__(self, cell):
        self.cell = cell
        self.model = cell.config
        self.t = cell.traffic
        self.counters = {"attempted": 0, "failed": 0, "completed": 0}
        self.bookkeeping_s = 0.0
        self.readings = {}
        self.trainer = None

    @contextlib.contextmanager
    def _bookkeeping(self):
        """Time spent only for the check, kept out of ``setup_s``."""
        t0 = time.perf_counter()
        yield
        self.bookkeeping_s += time.perf_counter() - t0

    def _run_to(self, steps: int):
        self.trainer.tcfg.steps = steps
        self.trainer.run()

    def setup(self):
        from repro.data.pipeline import DataConfig
        from repro.models.runtime import Runtime
        from repro.optim.optimizer import OptimizerConfig
        from repro.train.trainer import Trainer, TrainerConfig

        cfg = model_config(self.model)
        t = self.t
        self.trainer = Trainer(
            cfg, OptimizerConfig(**t["optimizer"]),
            DataConfig(vocab_size=cfg.vocab_size, seq_len=t["seq_len"],
                       global_batch=t["batch"], seed=self.cell.seed,
                       noise=t["noise"]),
            TrainerConfig(steps=0, seed=self.cell.seed, log_every=0),
            rt=Runtime(attn_impl=t["attn_impl"], compute_dtype=t["dtype"]))
        with self._bookkeeping():
            p0 = jax.device_get(self.trainer.params)
        self._run_to(1)
        with self._bookkeeping():
            scale = 1.0 / (1 - t["optimizer"]["beta1"])
            norms = jax.jit(reference.leaf_norms)(self.trainer.opt_state["m"])
            grad = {k: v * scale for k, v in
                    reference.flat_norms(jax.device_get(norms)).items()}
        self._run_to(CHECK_STEPS)
        with self._bookkeeping():
            p0 = jax.device_put(p0)
            moved = jax.jit(lambda a, b: reference.leaf_norms(
                jax.tree_util.tree_map(jnp.subtract, a, b)))(self.trainer.params, p0)
            moved = reference.flat_norms(jax.device_get(moved))
            del p0
            self.readings = {
                "loss": [m["loss"] for m in self.trainer.metrics_log],
                "grad": grad, "change": moved}
        self.tokens_per_step = t["batch"] * t["seq_len"]

    def window(self, seconds: float, tracer):
        steps, ends = 0, []
        tracer.tick()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self._run_to(self.trainer.step + 1)
            steps += 1
            ends.append(time.perf_counter())
            tracer.tick()
        self.window_s = ends[-1] - t0
        losses = [m["loss"] for m in self.trainer.metrics_log[-steps:]]
        bad = sum(not math.isfinite(x) for x in losses)
        self.counters.update(attempted=steps, failed=bad, completed=steps - bad,
                             window_s=self.window_s, t0=t0, step_ends=ends,
                             tokens_per_step=self.tokens_per_step,
                             trace_stopped=tracer.stopped)

    def end_to_end(self) -> dict:
        c = self.counters
        return {"train_tokens_per_s": c["attempted"] * self.tokens_per_step
                / self.window_s}

    def release(self):
        self.trainer = None
        gc.collect()

    def check(self) -> dict:
        t, m = self.t, self.model
        batches = [reference.token_batch(
            vocab=m["vocab_size"], seq_len=t["seq_len"], batch=t["batch"],
            seed=self.cell.seed, step=i, noise=t["noise"])
            for i in range(CHECK_STEPS)]
        ref = reference.lm_readings(m, t["optimizer"], batches, self.cell.seed)
        gaps = compare(self.readings, ref)
        self.counters["check"] = gaps.pop("_where")
        return {k: {"value": v, "limit": self.cell.limits[k]}
                for k, v in gaps.items()}


def program_readings_vs(ref_numerics: str, cell, fault=None) -> dict:
    """The reference put in the program's place: its readings at
    ``ref_numerics`` (and an optional planted ``fault``) compared with the
    float32 reference, as :func:`compare` would compare the program's."""
    t, m = cell.traffic, cell.config
    batches = [reference.token_batch(
        vocab=m["vocab_size"], seq_len=t["seq_len"], batch=t["batch"],
        seed=cell.seed, step=i, noise=t["noise"]) for i in range(CHECK_STEPS)]
    ref = reference.lm_readings(m, t["optimizer"], batches, cell.seed)
    other = reference.lm_readings(m, t["optimizer"], batches, cell.seed,
                                  numerics=ref_numerics, fault=fault)
    gaps = compare(other, ref)
    gaps.pop("_where")
    return gaps

