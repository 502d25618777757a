"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: the device's busy time, the operations that took most
of it, and the device's idle gaps named by what the host was doing.

:func:`load` turns the file into plain lists, :func:`summarize` reduces
them. Busy time is the union of the intervals in which an operation runs
on a device, averaged over the chips used. An idle stretch of a device is
attributed to the innermost host event open over it (the open event that
started last), or to ``"(no host event)"``.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict
from typing import Dict, List, Tuple

NO_HOST = "(no host event)"
#: the line of a device plane that holds its operations
OPS_LINE = "XLA Ops"

Event = Tuple[str, float, float]  # name, start ns, duration ns


def load(path) -> List[dict]:
    """``[{"name": plane, "lines": {line: [(event, start_ns, dur_ns)]}}]``."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _device_planes(planes):
    """Planes of accelerator chips, ``/device:TPU:<n>``, in chip order."""
    chips = [p for p in planes if re.fullmatch(r"/device:(TPU|GPU):\d+", p["name"])]
    return sorted(chips, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _ops(plane) -> List[Event]:
    lines = plane["lines"]
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    return [e for evs in lines.values() for e in evs]


def union(intervals) -> List[Tuple[float, float]]:
    """Disjoint sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _gaps(busy, lo, hi):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def attribute(gaps, host: List[Event]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each innermost open host event."""
    points = sorted({t for s, e in gaps for t in (s, e)}
                    | {t for _, s, d in host for t in (s, s + d)})
    order = sorted((s, s + d, name) for name, s, d in host if d > 0)
    out: Dict[str, float] = defaultdict(float)
    heap, i, g = [], 0, 0
    for a, b in zip(points, points[1:]):
        while i < len(order) and order[i][0] <= a:
            s, e, name = order[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= a:  # ended events leave from the top
            heapq.heappop(heap)
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps):
            break
        lo, hi = max(a, gaps[g][0]), min(b, gaps[g][1])
        if hi <= lo:
            continue
        out[heap[0][2] if heap else NO_HOST] += hi - lo
    return dict(out)


def summarize(planes: List[dict], n_chips: int, top: int = 10) -> dict:
    """``busy_s`` (mean over the first ``n_chips`` devices), ``window_s``,
    and the ``top`` device operations and idle gaps, in seconds."""
    devices = _device_planes(planes)[:n_chips]
    every = [(s, s + d) for p in planes for evs in p["lines"].values()
             for _, s, d in evs]
    if not devices or not every:
        return None
    lo, hi = min(s for s, _ in every), max(e for _, e in every)
    host = [e for p in planes if p["name"].startswith("/host:")
            for evs in p["lines"].values() for e in evs]
    busy_ns, op_ns, gap_ns = 0.0, defaultdict(float), defaultdict(float)
    for p in devices:
        ops = _ops(p)
        busy = union((s, s + d) for _, s, d in ops)
        busy_ns += sum(e - s for s, e in busy)
        for name, _, d in ops:
            op_ns[op_name(name)] += d
        for name, ns in attribute(_gaps(busy, lo, hi), host).items():
            gap_ns[name] += ns
    n = len(devices)
    rank = lambda d: [[k, v / 1e9 / n] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_ns / 1e9 / n, "window_s": (hi - lo) / 1e9,
            "device_ops": rank(op_ns), "idle_gaps": rank(gap_ns)}
