"""Tune driver: back-to-back kernel-tuning jobs through ``run_sweep``.

Each job tunes ``flash_attention``'s tiles at the cell's attention shape,
with the traffic file's engine and budget, a fresh in-memory TuningDB and a
seed drawn from the run's seed and the job's index. The window runs jobs
until ``--seconds`` have passed. A job counts once it has finished inside
the window; a trial counts once it has ended inside the window.

Every trial compiles cold, as it does for a user tuning a new shape: the
window runs with JAX's persistent compilation cache switched off, and a
listener counts the cache's hits all the same; a trial that saw one counts
as failed.

After the window the benchmark times each job's chosen tile and the default
tile (``block_q = block_kv = 128``, what ``ops.attention`` runs when the DB
has no record) with its own timer, and checks the kernel's output at each
chosen tile against the float32 reference.

The check's inputs are drawn from the seed as bfloat16 numbers held in
float32, with queries and keys of standard deviation 2 (logits of standard
deviation 4, as peaked as a trained model's). The MXU's one bfloat16 pass
then rounds no input, and what is left of the kernel's error is its own
arithmetic: attention computed wholly in bfloat16 reads many times more.
"""
from __future__ import annotations

import math
import statistics
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

KERNEL = "flash_attention"
DEFAULT_TILE = {"block_q": 128, "block_kv": 128}
CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: the index whose seed the set-up's warm-up job takes, apart from the window's
WARM_JOB = 2**31 - 1
#: the re-timing: rounds of alternating batches, and the least span of a batch
RETIME_ROUNDS = 3
RETIME_BATCH_S = 0.25
#: the share of the window the traced run profiles, from its start
TRACE_SECONDS = 10
#: standard deviation of the check's queries and keys
QK_STD = 2.0


class WindowClosed(BaseException):
    """Raised at the start of a trial once the window has closed; a
    ``BaseException`` so that the tuner's failure handling lets it pass."""


def attention_shape(model: dict, traffic: dict) -> dict:
    return {"B": traffic["batch"], "Sq": traffic["seq_len"],
            "Sk": traffic["seq_len"], "H": model["num_attention_heads"],
            "K": model["num_key_value_heads"], "dh": model["head_dim"]}


def job_seed(seed: int, index: int) -> int:
    return int(np.random.SeedSequence([seed, index]).generate_state(1)[0] >> 1)


def timed_evaluator(base, log: list, hits: list, deadline):
    """A subclass of the program's evaluator that records each call:
    its span, value, meta and the cache hits seen during it."""

    class Timed(base):
        def __call__(self, point, fidelity=None):
            start = time.perf_counter()
            if start >= deadline():
                raise WindowClosed()
            h0 = hits[0]
            value, meta = super().__call__(point, fidelity=fidelity)
            end = time.perf_counter()
            log.append({"start": start, "end": end, "value": value,
                        "point": dict(point), "meta": dict(meta),
                        "hits": hits[0] - h0})
            return value, meta

    return Timed


class _Instant:
    """Stands in for the evaluator while set-up warms the engine: a
    deterministic value per point, nothing compiled."""

    supports_fidelity = True
    returns_meta = True

    def __init__(self, *a, **k):
        pass

    def __call__(self, point, fidelity=None):
        return float(sum(int(v) for v in point.values()) % 97 + 1), {}


class Driver:
    trace_seconds = TRACE_SECONDS

    def __init__(self, cell):
        self.cell = cell
        self.t = cell.traffic
        self.shape = attention_shape(cell.config, self.t)
        self.counters = {"attempted": 0, "failed": 0, "completed": 0}
        self.bookkeeping_s = 0.0
        self.hits = [0]
        self.jobs = []
        self.trials = []

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT:
            self.hits[0] += 1

    def _sweep(self, evaluator_cls, db, seed):
        from benchmarks import kernel_sweep

        with mock.patch.object(kernel_sweep, "KernelTuneEvaluator", evaluator_cls):
            rows, _ = kernel_sweep.run_sweep(
                [KERNEL], db, budget=self.t["budget"],
                algorithm=self.t["algorithm"], shapes={KERNEL: self.shape},
                seed=seed, emit=lambda line: None)
        return rows[0]

    def setup(self):
        from repro.tuning.kernel_objective import KERNELS
        from repro.tuning.tundb import TuningDB

        jax.monitoring.register_event_listener(self._on_event)
        # the engine's own programs and the evaluator's input arrays are
        # compiled here, once; only the trials' kernels compile in the window
        self._sweep(_Instant, TuningDB(), job_seed(self.cell.seed, WARM_JOB))
        _, args, _ = KERNELS[KERNEL].build(self.shape, {})
        jax.block_until_ready(args)

    def window(self, seconds: float, tracer):
        from jax._src import compilation_cache
        from repro.tuning.kernel_objective import KernelTuneEvaluator
        from repro.tuning.tundb import TuningDB

        tracer.tick()
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def closes():
            tracer.tick()
            return deadline

        Timed = timed_evaluator(KernelTuneEvaluator, self.trials, self.hits, closes)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            index = 0
            while time.perf_counter() < deadline:
                db = TuningDB()
                try:
                    row = self._sweep(Timed, db, job_seed(self.cell.seed, index))
                except WindowClosed:
                    break
                end = time.perf_counter()
                if end <= deadline:
                    rec = db.lookup(KERNEL, self.shape)
                    self.jobs.append({"index": index, "best": row["best"],
                                      "value": row["value"],
                                      "recorded": rec["config"] if rec else None})
                index += 1
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        self._account(t0, deadline, seconds)

    def _account(self, t0, deadline, seconds):
        inside = [tr for tr in self.trials if tr["end"] <= deadline]
        failed = [tr for tr in inside
                  if tr["hits"] or not math.isfinite(tr["value"])]
        calls = sum(min(tr["end"], deadline) - max(tr["start"], t0)
                    for tr in self.trials if tr["start"] < deadline)
        self.counters.update(
            attempted=len(inside), failed=len(failed),
            completed=len(self.jobs), trials_ok=len(inside) - len(failed),
            cache_hits=sum(tr["hits"] for tr in self.trials),
            window_s=seconds,
            build_s=sum(tr["meta"].get("build_seconds", 0.0) for tr in inside),
            evaluator_s=calls)

    # -- after the window ----------------------------------------------------
    def _inputs(self):
        s = self.shape
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(self.cell.seed), 3)
        draws = (QK_STD * jax.random.normal(kq, (s["B"], s["Sq"], s["H"], s["dh"])),
                 QK_STD * jax.random.normal(kk, (s["B"], s["Sk"], s["K"], s["dh"])),
                 jax.random.normal(kv, (s["B"], s["Sk"], s["K"], s["dh"])))
        return tuple(x.astype(jnp.bfloat16).astype(jnp.float32) for x in draws)

    def _compiled(self, tile):
        from repro.kernels import ops

        return jax.jit(lambda q, k, v: ops.attention(
            q, k, v, causal=True, impl="pallas", block_q=int(tile["block_q"]),
            block_kv=int(tile["block_kv"])))

    def _per_call(self, fn, args, n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    def retime(self, tiles):
        """Median per-call seconds of each tile and of the default, timed in
        alternating batches of back-to-back calls that each span at least
        ``RETIME_BATCH_S``."""
        args = self._inputs()
        fns, outs, calls = {}, {}, {}
        for tile in [DEFAULT_TILE] + tiles:
            key = _key(tile)
            if key in fns:
                continue
            fns[key] = self._compiled(tile)
            outs[key] = jax.block_until_ready(fns[key](*args))
            once = self._per_call(fns[key], args, 3)
            calls[key] = max(1, math.ceil(RETIME_BATCH_S / once))
        times = {k: [] for k in fns}
        dflt = _key(DEFAULT_TILE)
        for key in fns:
            if key == dflt:
                continue
            for _ in range(RETIME_ROUNDS):
                for k in (dflt, key):
                    times[k].append(self._per_call(fns[k], args, calls[k]))
        med = {k: statistics.median(v) for k, v in times.items() if v}
        if dflt not in med:
            med[dflt] = self._per_call(fns[dflt], args, calls[dflt])
        return med, outs, args

    def end_to_end(self) -> dict:
        c = self.counters
        e2e = {"tune_trials_per_s": c["trials_ok"] / c["window_s"]}
        if not self.jobs:
            return e2e
        tiles = [j["recorded"] or j["best"] for j in self.jobs]
        med, self.outs, self.args = self.retime(tiles)
        dflt = med[_key(DEFAULT_TILE)]
        speedups, errors = [], []
        examples = self.shape["B"] * self.shape["Sq"]
        for j, tile in zip(self.jobs, tiles):
            t = med[_key(tile)]
            speedups.append(dflt / t)
            errors.append(abs(examples / j["value"] - t) / t)
        e2e["tuned_speedup"] = math.exp(sum(map(math.log, speedups)) / len(speedups))
        c.update(speedups=speedups, objective_errors=errors,
                 tile_seconds={k: v for k, v in med.items()})
        return e2e

    def release(self):
        jax.monitoring.unregister_event_listener(self._on_event)

    def check(self) -> dict:
        lim = self.cell.limits
        mismatch = sum(j["recorded"] is None or _key(j["recorded"]) != _key(j["best"])
                       for j in self.jobs)
        worst = {"attn_err": math.inf, "attn_rms": math.inf}
        if self.jobs:
            q, k, v = self.args
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, k, v: reference.attention(
                    q, k, v, dot=reference.make_dot("f32")))(q, k, v)
            errs = [attention_errors(self.outs[_key(j["recorded"] or j["best"])], ref)
                    for j in self.jobs]
            worst = {n: max(e[n] for e in errs) for n in worst}
        out = {n: {"value": v, "limit": lim[n]} for n, v in worst.items()}
        out["tile_mismatch"] = {"value": float(mismatch), "limit": lim["tile_mismatch"]}
        return out


def attention_errors(out, ref) -> dict:
    """The kernel's output against the reference's: the largest error and
    the root-mean-square error, each over the reference's own scale."""
    diff = out - ref
    return {"attn_err": float(jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(ref))),
            "attn_rms": float(jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(ref * ref)))}


def _key(tile) -> tuple:
    return (int(tile["block_q"]), int(tile["block_kv"]))
