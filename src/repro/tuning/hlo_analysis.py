"""HLO text analysis: collective bytes + scan-aware cost extraction.

``compiled.cost_analysis()`` gives per-device HLO FLOPs/bytes;
collective traffic is NOT in cost_analysis, so we parse the (post-SPMD,
per-device) HLO text and sum operand bytes of every collective op,
multiplying ops inside ``while`` loop bodies by the loop trip count
(scan-over-layers!).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_TRIP_RE = re.compile(r"trip_count=(\d+)")
# e.g.: %fusion.1 = (f32[8,128]{1,0}, ...) all-gather(...)
_OP_RE = re.compile(r"=\s+(\([^)]*\)|\S+)\s+([\w-]+)(\.\d+)?\(")


def shape_bytes(shape_str: str) -> int:
    """Sum bytes over every tensor literal in an HLO shape string."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    count_by_kind: Dict[str, int] = field(default_factory=lambda: defaultdict(int))

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    def summary(self) -> str:
        if not self.bytes_by_kind:
            return "none"
        parts = [
            f"{k}:{self.count_by_kind[k]}x/{self.bytes_by_kind[k]/1e6:.1f}MB"
            for k in sorted(self.bytes_by_kind)
        ]
        return " ".join(parts)


def _computation_blocks(hlo: str) -> Dict[str, str]:
    """Split HLO text into computation bodies keyed by computation name."""
    blocks: Dict[str, str] = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        stripped = line.strip()
        is_header = stripped.endswith("{") and "->" in stripped and "=" not in stripped.split("(")[0]
        if is_header:
            if cur_name is not None:
                blocks[cur_name] = "\n".join(cur_lines)
            name = stripped.split("(")[0].strip()
            if name.startswith("ENTRY"):
                name = name[len("ENTRY"):].strip()
            cur_name, cur_lines = name.lstrip("%").strip(), []
        elif stripped.startswith("}"):
            if cur_name is not None:
                blocks[cur_name] = "\n".join(cur_lines)
                cur_name, cur_lines = None, []
        elif cur_name is not None:
            cur_lines.append(line)
    if cur_name is not None:
        blocks[cur_name] = "\n".join(cur_lines)
    return blocks


def _condition_bound(cond: str) -> Optional[int]:
    """N where a while condition's root is ``compare(i, N), direction=LT``
    against an integer constant: the trip count of a loop from 0 by 1, as
    JAX's scans and fori_loops run."""
    root = re.search(r"ROOT .*compare\(.*%([\w\.\-]+)\), direction=LT", cond)
    if not root:
        return None
    const = re.search(rf"%{re.escape(root.group(1))} = s32\[\]\S* constant\((\d+)\)",
                      cond)
    return int(const.group(1)) if const else None


def _own_trips(blocks: Dict[str, str]) -> Dict[str, int]:
    """While-body name -> its own trip count.

    XLA annotates `while` ops with backend_config known_trip_count after
    simplification; where a compiled module has dropped it (the TPU
    compiler's does), the bound of the loop's ``i < N`` condition on a
    constant stands in (:func:`_condition_bound`)."""
    own_trip: Dict[str, int] = {}
    for text in blocks.values():
        for line in text.splitlines():
            if " while(" not in line:
                continue
            body = re.search(r"body=%?([\w\.\-_]+)", line)
            if not body:
                continue
            kt = re.search(r'"known_trip_count":\s*\{"n":"?(\d+)"?\}', line)
            if not kt:
                kt = _TRIP_RE.search(line)
            cond = re.search(r"condition=%?([\w\.\-_]+)", line)
            own_trip[body.group(1)] = (int(kt.group(1)) if kt else
                                       _condition_bound(blocks.get(cond.group(1), "")
                                                        if cond else "") or 1)
    return own_trip


_CALLEE_RE = re.compile(r"\b(body|calls)=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _call_multipliers(blocks: Dict[str, str]) -> Dict[str, int]:
    """Times each computation runs per execution of the module: a while
    body its trip count times its caller's (a layer scan inside a
    microbatch loop runs trips_layer x trips_mb times); a computation
    called by a fusion, an async op or a conditional as often as its
    caller. The TPU compiler puts most collectives inside such called
    computations (``async_collective_fusion``, ``all-reduce-scatter``)."""
    own_trip = _own_trips(blocks)
    callers: Dict[str, list] = defaultdict(list)  # callee -> [(caller, factor)]
    for name, text in blocks.items():
        for kind, callee in _CALLEE_RE.findall(text):
            callers[callee].append((name, own_trip.get(callee, 1) if kind == "body" else 1))
        for group in _BRANCHES_RE.findall(text):
            for callee in re.findall(r"%?([\w\.\-]+)", group):
                callers[callee].append((name, 1))
    memo: Dict[str, int] = {}

    def mult(block: str) -> int:
        if block not in memo:
            memo[block] = (sum(mult(p) * f for p, f in callers[block])
                           if block in callers else 1)
        return memo[block]

    return {name: mult(name) for name in blocks}


def collect_collective_stats(hlo: str) -> CollectiveStats:
    """Sum collective operand bytes, scaling while-body ops by trip count
    (and the computations they call by their caller's count).

    The TPU compiler copies one collective into the fusions that start it
    and continue it, all under its ``channel_id``: a channel counts once.
    An all-reduce inside an ``all-reduce-scatter`` computation, that
    compiler's reduce-scatter, counts as a reduce-scatter."""
    stats = CollectiveStats()
    blocks = _computation_blocks(hlo)
    mults = _call_multipliers(blocks)
    found: Dict[tuple, tuple] = {}  # channel (or the line) -> (kind, bytes, mult)
    for name, text in blocks.items():
        for i, line in enumerate(text.splitlines()):
            for kind in _COLLECTIVE_KINDS:
                # ops appear as `kind(`, `kind.N(`, or `kind-start(`
                if re.search(rf"=.*\s{kind}(?:-start)?(?:\.\d+)?\(", line):
                    # operand bytes = result shape bytes (collectives are
                    # shape-preserving except all-gather: use result which
                    # upper-bounds traffic) — take the shape on the lhs.
                    lhs = line.split("=", 1)[1] if "=" in line else line
                    shape_part = lhs.strip().split(" ", 1)[0]
                    if kind == "all-reduce" and name.startswith("all-reduce-scatter"):
                        kind = "reduce-scatter"
                    channel = re.search(r"channel_id=(\d+)", line)
                    key = ("channel", channel.group(1)) if channel else (name, i)
                    if key not in found or found[key][2] < mults[name]:
                        found[key] = (kind, shape_bytes(shape_part), mults[name])
                    break
    for kind, b, mult in found.values():
        stats.bytes_by_kind[kind] += b * mult
        stats.count_by_kind[kind] += mult
    return stats


_NO_TRAFFIC_OPS = {
    "tuple", "get-tuple-element", "bitcast", "parameter", "constant",
    "after-all", "partition-id", "replica-id", "iota", "reshape",
    "optimization-barrier",
}

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*((?:\([^=]*?\)|\S+))\s+([\w\-]+)(?:\.\d+)?\(")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_META_RE = re.compile(r'op_name="([^"]*)"')


@dataclass
class TrafficStats:
    """Per-op HBM traffic accounting over the optimized per-device HLO.

    For every instruction in *executable* computations (entry + while
    bodies, the latter scaled by known trip counts; fusion internals are
    skipped — the fusion call-site's external operands/outputs count),
    traffic = output bytes + sum(operand bytes).  Pure-aliasing ops are
    skipped.  Ops whose metadata carries a ``krnl_`` scope (regions the
    Pallas kernels keep in VMEM on the real target) are bucketed
    separately so the roofline memory term can credit them with their
    true HBM traffic instead of the CPU-unfused op chain."""

    included_bytes: float = 0.0
    excluded_bytes: float = 0.0
    excluded_by_tag: Dict[str, float] = field(default_factory=lambda: defaultdict(float))


def traffic_analysis(hlo: str, exclude_substr: tuple = ("krnl_",)) -> TrafficStats:
    # pass 1: def table name -> bytes
    def_bytes: Dict[str, int] = {}
    for line in hlo.splitlines():
        m = _DEF_RE.match(line)
        if m:
            def_bytes[m.group(1)] = shape_bytes(m.group(2))

    blocks = _computation_blocks(hlo)
    mults = _call_multipliers(blocks)
    # executable computations: ENTRY + while bodies; fusion internals out
    bodies = _own_trips(blocks)
    entry = _entry_names(hlo) if "ENTRY" in hlo else set()
    exec_blocks = {name: mults[name] for name in blocks
                   if name in bodies or name in entry}
    stats = TrafficStats()
    for name, mult in exec_blocks.items():
        for line in blocks[name].splitlines():
            m = _DEF_RE.match(line)
            if not m:
                continue
            out_name, shape_str, op_kind = m.groups()
            if op_kind in _NO_TRAFFIC_OPS or op_kind == "while":
                continue
            out_b = shape_bytes(shape_str)
            operand_b = 0
            args_part = line.split("(", 1)[1] if "(" in line else ""
            args_part = args_part.split("metadata=")[0]
            for om in _OPERAND_RE.finditer(args_part):
                operand_b += def_bytes.get(om.group(1), 0)
            total = (out_b + operand_b) * mult
            meta = _META_RE.search(line)
            tag = None
            if meta:
                for sub in exclude_substr:
                    idx = meta.group(1).find(sub)
                    if idx >= 0:
                        tag = meta.group(1)[idx:].split("/")[0]
                        break
            if tag:
                stats.excluded_bytes += total
                stats.excluded_by_tag[tag] += total
            else:
                stats.included_bytes += total
    return stats


def _entry_names(hlo: str) -> set:
    out = set()
    for line in hlo.splitlines():
        m = re.match(r"ENTRY\s+%?([\w\.\-]+)", line.strip())
        if m:
            out.add(m.group(1))
    return out


def cost_with_scan_correction(compiled, hlo: Optional[str] = None) -> Dict[str, float]:
    """compiled.cost_analysis() flops/bytes.  XLA's HloCostAnalysis already
    multiplies while-body cost by trip count when it is statically known
    (verified empirically in tests/test_hlo_analysis.py); this wrapper just
    normalizes key names across jax versions."""
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jax returns [dict]
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes": bytes_accessed, "raw": dict(ca)}
