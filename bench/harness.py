"""The benchmark harness: one run of one cell.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything the
harness needs for it is found by name:

* ``bench/configs/<config>.json``   the model configuration as run (the
  ``file`` of its ``configs`` entry);
* ``bench/traffic/<traffic>.json``  the job's parameters; its ``driver`` key
  names ``bench/drivers/<driver>.py``;
* ``bench/limits/<workload>.json``  the limit of each number compared;
* ``bench/metrics/<name>.py``       one reader per per-layer metric.

A driver module exposes ``Driver(cell)`` with ``setup()``, ``window(seconds,
tracer)``, ``end_to_end()``, ``release()``, ``check()``, a ``counters`` dict
that the metric readers read, ``bookkeeping_s``: set-up time spent only
for the check, left out of ``setup_s``, and ``trace_seconds``: how much of
the window a traced run profiles. A reader exposes ``read(ctx)`` and
returns a number, or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    """One workload, resolved to its files."""

    name: str
    chips: int
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    limits: Dict[str, float]
    seed: int  # derived from the run's --seed, see derive_seed
    end_to_end: List[dict]
    per_layer: List[dict]
    run_dir: pathlib.Path


def derive_seed(seed: int) -> int:
    """A 31-bit seed for the program and the reference, drawn from the run's
    ``--seed`` (any non-negative integer). JAX's default keys keep 32 bits
    of a seed, so larger seeds would otherwise alias."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A per-layer metric with ``workloads`` is read in those cells; one
    without is read wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def resolve(workload: str, seed: int, root: pathlib.Path = ROOT) -> Cell:
    doc = load_benchmark(root)
    cells = {w["name"]: w for w in doc["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in doc["configs"]}[w["config"]]
    e2e = [m for m in doc["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in doc["per_layer"] if _applies(m, workload, reported)]
    bench = root / "bench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{workload}.json").read_text()),
        seed=derive_seed(seed), end_to_end=e2e, per_layer=per_layer,
        run_dir=root / ".bench_run" / workload)


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(kind: str, bench: pathlib.Path = BENCH):
    """``bench/drivers/<kind>.py``."""
    return _module(bench / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def load_reader(name: str, bench: pathlib.Path = BENCH) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    return _module(bench / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def peaks(device_kind: str, bench: pathlib.Path = BENCH) -> dict:
    table = json.loads((bench / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json knows {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# Devices and the trace
# ---------------------------------------------------------------------------


def accelerators(chips: int):
    """The first ``chips`` accelerator devices, or :class:`NoChip`."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> Optional[int]:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return int(max(peaks_)) if peaks_ else None


class Tracer:
    """Profiles the first ``seconds`` of the window when switched on. The
    driver calls :meth:`tick` between units of work."""

    def __init__(self, enabled: bool, seconds: float, out_dir: pathlib.Path):
        self.enabled = enabled
        self.seconds = seconds
        self.out_dir = out_dir
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None

    def tick(self):
        if not self.enabled or self.stopped is not None:
            return
        import jax

        now = time.perf_counter()
        if self.started is None:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 2
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
            self.started = time.perf_counter()
        elif now - self.started >= self.seconds:
            self.stop()

    def stop(self):
        if self.started is not None and self.stopped is None:
            import jax

            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()

    def summary(self, n_chips: int) -> Optional[dict]:
        if self.stopped is None:
            return None
        from bench import trace

        files = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        return trace.summarize(trace.load(files[-1]), n_chips=n_chips)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def _num(x):
    """A finite float, or None: JSON has no infinities."""
    return float(x) if x is not None and math.isfinite(float(x)) else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        root: pathlib.Path = ROOT, log=lambda s: print(s, file=sys.stderr,
                                                       flush=True)) -> dict:
    """Set up, measure for ``seconds``, check; returns the result object."""
    cell = resolve(workload, seed, root)
    import jax

    devices = accelerators(cell.chips) if require_chip else jax.devices()[:cell.chips]
    cell.run_dir.mkdir(parents=True, exist_ok=True)
    driver = load_driver(cell.traffic["driver"], root / "bench").Driver(cell)
    driver.setup()
    setup_s = time.perf_counter() - t_start - driver.bookkeeping_s
    tracer = Tracer(trace, float(driver.trace_seconds), cell.run_dir / "trace")
    try:
        driver.window(seconds, tracer)
    finally:
        tracer.stop()
    e2e = driver.end_to_end()
    mem = memory_peak(devices) if require_chip else None
    summary = tracer.summary(len(devices)) if trace else None
    driver.release()
    gc.collect()
    t_check = time.perf_counter()
    compared = driver.check()
    log(f"check_s {time.perf_counter() - t_check!r}")

    correct = bool(compared) and all(
        c["value"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in compared.values())
    correct = correct and driver.counters.get("completed", 0) > 0
    log(f"setup_s {setup_s!r}; " + ", ".join(
        f"{k}={v!r}" for k, v in driver.counters.items()
        if isinstance(v, (int, float, str)) or (isinstance(v, list) and len(v) <= 20)))
    for k, c in compared.items():
        log(f"compared {k}: {c['value']!r} (limit {c['limit']!r})")

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        ctx = {"cell": cell, "counters": driver.counters, "trace": summary,
               "device_kind": devices[0].device_kind, "n_chips": len(devices),
               "root": root}
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"], root / "bench")(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": _num(v), "unit": units[m["name"]]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": _num(e2e[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct,
              "attempted": int(driver.counters.get("attempted", 0)),
              "failed": int(driver.counters.get("failed", 0)),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["compared"] = {k: {"value": _num(c["value"]), "limit": c["limit"]}
                          for k, c in compared.items()}
    return result
