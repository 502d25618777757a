"""What the program names inside a traced run: the device's idle time under
its host spans, and the device time of its kernel scopes.

:func:`idle_under` puts each idle stretch of a device down to every host
span of a given name that is open over it, at any depth and on any
thread, where :func:`bench.trace.attribute` names only the innermost.
:func:`scope_seconds` reads each device operation's self time and
framework scope from the trace through ``xprof``'s ``hlo_stats`` tool: a
``while`` op's self time leaves out the ops of its body, so nothing is
counted twice.
"""
from __future__ import annotations

import functools
import json
import pathlib
from typing import Dict, Iterable, Optional

from bench import trace


def trace_file(run_dir: pathlib.Path) -> Optional[pathlib.Path]:
    """The profile a traced run wrote under ``<run_dir>/trace``, found as
    :meth:`bench.harness.Tracer.summary` finds it."""
    files = sorted((run_dir / "trace").glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


@functools.lru_cache(maxsize=1)
def _planes(path: str, mtime_ns: int):
    return trace.load(path)


def planes(path: pathlib.Path):
    """:func:`bench.trace.load`, kept for the next reader of the same file."""
    return _planes(str(path), path.stat().st_mtime_ns)


def _overlap(a, b) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(planes, names: Iterable[str], n_chips: int) -> Dict[str, float]:
    """Seconds, as a mean over the first ``n_chips`` devices, in which the
    device is idle while a host event of each name is open. The stretch is
    :func:`bench.trace.summarize`'s. A name with no host event, or a trace
    with no device, is left out of the result."""
    devices = trace._device_planes(planes)[:n_chips]
    every = [(s, s + d) for p in planes for evs in p["lines"].values()
             for _, s, d in evs]
    if not devices or not every:
        return {}
    lo, hi = min(s for s, _ in every), max(e for _, e in every)
    wanted = set(names)
    spans = {n: [] for n in wanted}
    for p in planes:
        if p["name"].startswith("/host:"):
            for evs in p["lines"].values():
                for name, s, d in evs:
                    if name in wanted:
                        spans[name].append((s, s + d))
    idle = [trace._gaps(trace.union((s, s + d) for _, s, d in trace._ops(p)),
                        lo, hi) for p in devices]
    return {n: sum(_overlap(g, trace.union(iv)) for g in idle) / 1e9 / len(devices)
            for n, iv in spans.items() if iv}


def scope_seconds(path: pathlib.Path, scope: str, n_chips: int) -> Optional[float]:
    """Device self time, in seconds as a mean over ``n_chips`` devices, of
    the operations whose framework scope contains ``scope``; ``None`` where
    the trace holds no such operation or ``xprof`` cannot read it. The tool
    caches its reading as ``ALL_HOSTS.op_stats.pb`` beside the trace."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return None
    data, _ = raw_to_tool_data.xspace_to_tool_data([str(path)], "hlo_stats", {})
    if not data:
        return None
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    at_name, at_self = cols.index("tf_op_name"), cols.index("total_self_time")
    micros = [row["c"][at_self]["v"] for row in table["rows"]
              if scope in (row["c"][at_name]["v"] or "")]
    if not micros:
        return None
    return sum(micros) / 1e6 / n_chips


def idle_share(ctx, span: str) -> Optional[float]:
    """A reader's value: the share, in %, of the traced stretch in which the
    device is idle under ``span`` (:func:`idle_under`); ``None`` where the
    run has no device trace or recorded no such span."""
    s, path = ctx["trace"], trace_file(ctx["cell"].run_dir)
    if not s or s["window_s"] <= 0 or path is None:
        return None
    idle = idle_under(planes(path), [span], ctx["n_chips"])
    return 100.0 * idle[span] / s["window_s"] if span in idle else None


def scope_share(ctx, scope: str) -> Optional[float]:
    """A reader's value: the share, in %, of the traced stretch that the
    device spends in operations under ``scope`` (:func:`scope_seconds`)."""
    s, path = ctx["trace"], trace_file(ctx["cell"].run_dir)
    if not s or s["window_s"] <= 0 or path is None:
        return None
    seconds = scope_seconds(path, scope, ctx["n_chips"])
    return None if seconds is None else 100.0 * seconds / s["window_s"]
