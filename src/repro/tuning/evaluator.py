"""Objective evaluators — the "system under test" side of paper Fig. 4.

* ``WallClockEvaluator`` — the paper-faithful measurement path: apply the
  configuration, run the jitted step on the local device(s), report
  measured throughput (examples- or tokens-/second).
* ``RooflineEvaluator`` — the TPU-shaped path for this CPU-only container:
  lower+compile the production-mesh program for the configuration and
  report the roofline-estimated throughput (tokens/second).  A
  configuration whose per-device footprint exceeds HBM is a *failed run*
  (-inf), exactly like a crashed measurement in the paper's harness.
  ``cache_path`` persists every compile+analysis through the shared
  :class:`~repro.tuning.cache.JsonCacheStore` (atomic writes,
  cross-process file locking), so concurrent tuning runs — even on
  different hosts sharing a filesystem — merge their measurements
  instead of clobbering each other; the on-disk format is unchanged
  from the historical plain-JSON cache.

Both implement the explicit evaluator protocol
(``repro.tuning.objective.Evaluator``): ``__call__(point) -> (value,
meta)``, declared via ``returns_meta = True`` so the tuner/executor never
have to sniff return types.  Both also opt into the **fidelity**
protocol (``supports_fidelity``) for multi-fidelity tuning:
``WallClockEvaluator`` scales its variance-adaptive timing loop,
``RooflineEvaluator`` drops to the fast (single-compile, trip-scaled)
analysis depth; in both, a full-fidelity request takes exactly the same
code path as a plain no-fidelity call.  (Note the *measurement loop
itself* changed in this revision: ``WallClockEvaluator`` now defaults to
variance-adaptive timing — pass ``adaptive=False`` for the historical
fixed-``iters`` loop.)
"""
from __future__ import annotations

import contextlib
import json
import math
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.tuning.cache import CacheStore, open_store
from repro.tuning.cost_model import HBM_BYTES
from repro.tuning.objective import Evaluator
from repro.tuning.parameters import BASELINE, BackendConfig, config_from_point


class RooflineEvaluator(Evaluator):
    def __init__(
        self,
        arch: str,
        shape_name: str,
        *,
        multi_pod: bool = False,
        chips_per_pod: int = 256,
        base: BackendConfig = BASELINE,
        hbm_bytes: float = HBM_BYTES,
        cache_path: Optional[str] = None,
    ):
        self.arch = arch
        self.shape_name = shape_name
        self.multi_pod = multi_pod
        self.chips_per_pod = chips_per_pod
        self.base = base
        self.hbm_bytes = hbm_bytes
        # the shared store is loaded exactly once here; later in-memory
        # misses re-consult it (a locked file read) before compiling, so
        # entries written by concurrent hosts after startup are reused
        self.store: CacheStore = open_store(cache_path)
        self._cache: Dict[str, dict] = self.store.load()

    supports_fidelity = True

    def _key(self, bc: BackendConfig, fast: bool = False) -> str:
        d = {"arch": self.arch, "shape": self.shape_name, "mp": self.multi_pod,
             "bc": bc.__dict__}
        if fast:  # full-fidelity keys keep the historical format unchanged
            d["analysis"] = "fast"
        return json.dumps(d, sort_keys=True)

    def __call__(self, point: Dict,
                 fidelity: Optional[float] = None) -> Tuple[float, dict]:
        from repro.launch.dryrun import analyze_cell  # lazy: sets XLA_FLAGS

        # analysis-depth fidelity: a partial measurement drops the unrolled
        # 1-/2-period cost compiles (``fast`` analysis — trip-count scaling,
        # a documented few-% overcount) instead of the exact extrapolation,
        # cutting the per-point compile count from three to one
        fast = fidelity is not None and fidelity < 1.0
        bc = config_from_point(point, self.base)
        key = self._key(bc, fast=fast)
        rec = self._cache.get(key)
        if rec is None:
            # in-memory miss: another host sharing this store may have
            # compiled it since __init__ — a locked file read is orders of
            # magnitude cheaper than a recompile.  The whole snapshot was
            # just parsed anyway, so merge every entry we don't already
            # hold: each concurrent-host record then costs one file read
            # total, not one per miss
            for k, v in self.store.load().items():
                self._cache.setdefault(k, v)
            rec = self._cache.get(key)
        if rec is None:
            rec = analyze_cell(
                self.arch, self.shape_name, multi_pod=self.multi_pod,
                bc=bc, chips_per_pod=self.chips_per_pod, fast=fast,
            )
            self._cache[key] = rec
            # merge-on-write under the store's file lock: concurrent tuning
            # runs sharing one cache file union their entries
            self.store.put(key, rec)
        # a full-fidelity request is byte-identical to a plain call,
        # meta included; only partial measurements are labeled
        fid_meta = {"fidelity": float(fidelity)} if fast else {}
        if rec.get("skipped"):
            return -math.inf, dict(fid_meta, skip_reason=rec["skip_reason"])
        mem = rec["memory"]["per_device_B"]
        meta = dict(fid_meta, roofline=rec["roofline"], mem_per_device_B=mem)
        if mem > self.hbm_bytes:
            return -math.inf, dict(meta, oom=True)
        return float(rec["roofline"]["throughput_tok_s"]), meta


#: JAX's compile events, and the meta key that each one's seconds go to
PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_seconds",
    "/jax/core/compile/backend_compile_duration": "compile_seconds",
}
#: process-wide sums over every ``WallClockEvaluator`` call that returned:
#: the counters a long-lived worker exports
PHASE_TOTALS = dict({"calls": 0, "build_seconds": 0.0},
                    **dict.fromkeys(PHASE_EVENTS.values(), 0.0))
_phases_local = threading.local()
_phases_lock = threading.Lock()
_listening = False


def _on_compile_span(event: str, start: float, end: float, **_):
    spans = getattr(_phases_local, "spans", None)
    if spans is not None and event in PHASE_EVENTS:
        spans.append((start, end, PHASE_EVENTS[event]))


def _outermost(spans) -> Dict[str, float]:
    """Seconds under each phase, each instant counted once, for the
    outermost span open over it: a jit traced while a Pallas kernel is
    lowered is lowering, and nested traces are not counted twice."""
    out = dict.fromkeys(PHASE_EVENTS.values(), 0.0)
    covered = -math.inf
    for start, end, key in sorted(spans, key=lambda s: (s[0], -s[1])):
        if end > covered:
            out[key] += end - max(start, covered)
            covered = end
    return out


@contextlib.contextmanager
def compile_phases():
    """Yields a dict that, when the block exits, holds the seconds this
    thread spent inside it tracing, lowering and compiling, by JAX's own
    compile events (keys: ``PHASE_EVENTS``' values).  The listener is
    registered once per process and records only into the calling
    thread's open block: a compile on another thread, or outside any
    block, adds nothing."""
    global _listening
    with _phases_lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_compile_span)
            _listening = True
    outer = getattr(_phases_local, "spans", None)
    spans = _phases_local.spans = []
    phases = dict.fromkeys(PHASE_EVENTS.values(), 0.0)
    try:
        yield phases
    finally:
        _phases_local.spans = outer
        phases.update(_outermost(spans))


#: two-sided 95% Student-t critical values by degrees of freedom (1-30);
#: beyond 30 the normal 1.96 is within ~2%
_T95 = (12.706, 4.303, 3.182, 2.776, 2.571, 2.447, 2.365, 2.306, 2.262,
        2.228, 2.201, 2.179, 2.160, 2.145, 2.131, 2.120, 2.110, 2.101,
        2.093, 2.086, 2.080, 2.074, 2.069, 2.064, 2.060, 2.056, 2.052,
        2.048, 2.045, 2.042)


def _t95(df: int) -> float:
    return _T95[df - 1] if 1 <= df <= len(_T95) else 1.96


class WallClockEvaluator(Evaluator):
    """Measured throughput of a step built from the configuration point.

    ``make_step(point) -> (step_fn, args, examples_per_step)``:
    the builder applies the point's backend parameters (Runtime knobs,
    microbatches, ...) and returns a jittable step plus its inputs.

    Measurement is **variance-adaptive**: steps are timed one at a time
    until the 95% confidence half-width of the mean step time is within
    ``rel_halfwidth`` of the mean, or ``max_iters`` measurements were
    taken — so a stable configuration stops after ``min_iters`` steps
    while a jittery one keeps measuring up to the cap.  The caps default
    off the caller's ``iters`` (``min_iters = 2`` — the CI needs two
    samples — and ``max_iters = 4 * iters``), so a harness sized for cheap
    measurements stays cheap: ``iters=3`` now usually costs 2 steps and
    never more than 12.  Note the methodology: per-step variance needs a
    per-step ``block_until_ready``, so each sample includes one
    host/device sync that the historical pipelined loop amortized across
    ``iters`` steps — for sub-millisecond steps this inflates
    ``step_seconds`` slightly and uniformly.  ``adaptive=False`` restores
    the historical fixed-``iters`` pipelined loop exactly (use it when
    numbers must be comparable with pre-adaptive runs).

    Fidelity (``supports_fidelity``): a partial measurement scales the
    iteration cap by ``fidelity`` and widens the target CI by
    ``1/fidelity`` — the bottom successive-halving rung is a couple of
    quick steps with a loose interval, the top rung the full adaptive
    loop.  ``fidelity=None``/1.0 is byte-identical to a plain call.

    Cost attribution: ``meta["cost_seconds"]`` is the **measurement-only**
    time (the timing loop), excluding step build, jit lowering/compile,
    and warmup — a repeat measurement of this configuration pays only the
    timing loop, so charging compile to the configuration would mislead
    cost-aware (EI-per-second) acquisition.  The one-time overhead is
    reported separately as ``meta["build_seconds"]``, and split by JAX's
    own compile events (:func:`compile_phases`) into
    ``meta["trace_seconds"]``, ``meta["lower_seconds"]`` and
    ``meta["compile_seconds"]``; the rest of the build is the builder's
    own work and the warm-up call.
    """

    supports_fidelity = True

    def __init__(
        self,
        make_step: Callable[[Dict], Tuple[Callable, tuple, float]],
        *,
        warmup: int = 1,
        iters: int = 3,
        adaptive: bool = True,
        rel_halfwidth: float = 0.05,
        min_iters: Optional[int] = None,
        max_iters: Optional[int] = None,
    ):
        self.make_step = make_step
        self.warmup = warmup
        self.iters = iters
        self.adaptive = adaptive
        self.rel_halfwidth = rel_halfwidth
        # caps scale with the caller's iters so harnesses sized for cheap
        # measurements stay cheap; the CI needs >= 2 samples for a
        # variance estimate, so 2 is the floor either way
        self.max_iters = max(2, 4 * iters if max_iters is None else max_iters)
        self.min_iters = min(self.max_iters,
                             max(2, 2 if min_iters is None else min_iters))

    def _measure(self, jitted, args, fidelity: float):
        """Adaptive timing loop: per-step seconds list."""
        if not self.adaptive:
            n = max(1, round(self.iters * fidelity))
            t0 = time.perf_counter()
            out = None
            for _ in range(n):
                out = jitted(*args)
            jax.block_until_ready(out)
            return [(time.perf_counter() - t0) / n] * n
        cap = max(self.min_iters, math.ceil(self.max_iters * fidelity))
        target = self.rel_halfwidth / fidelity
        times = []
        while len(times) < cap:
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(*args))
            times.append(time.perf_counter() - t0)
            n = len(times)
            if n < self.min_iters:
                continue
            mean = sum(times) / n
            var = sum((t - mean) ** 2 for t in times) / (n - 1)
            halfwidth = _t95(n - 1) * math.sqrt(var / n)
            if halfwidth <= target * mean:
                break
        return times

    def __call__(self, point: Dict,
                 fidelity: Optional[float] = None) -> Tuple[float, dict]:
        f = 1.0 if fidelity is None else max(min(float(fidelity), 1.0), 1e-3)
        t_build0 = time.perf_counter()
        with compile_phases() as phases:
            step, args, examples = self.make_step(point)
            jitted = jax.jit(step)
            out = None
            for _ in range(self.warmup):
                out = jitted(*args)
            jax.block_until_ready(out)
        build_seconds = time.perf_counter() - t_build0
        times = self._measure(jitted, args, f)
        n = len(times)
        dt = sum(times) / n
        mean = dt
        hw = 0.0
        if n >= 2:
            var = sum((t - mean) ** 2 for t in times) / (n - 1)
            hw = _t95(n - 1) * math.sqrt(var / n)
        meta = {
            "step_seconds": dt,
            "iters": n,
            "ci_rel_halfwidth": hw / mean if mean > 0 else 0.0,
            "build_seconds": build_seconds,
            **phases,
            # measurement-only cost: what a repeat measurement would pay
            "cost_seconds": float(sum(times)),
        }
        with _phases_lock:
            PHASE_TOTALS["calls"] += 1
            PHASE_TOTALS["build_seconds"] += build_seconds
            for k, v in phases.items():
                PHASE_TOTALS[k] += v
        if f < 1.0:  # a full-fidelity request is byte-identical to a
            meta["fidelity"] = f  # plain call, meta included
        return examples / dt, meta
