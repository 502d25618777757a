"""Share of the traced stretch of the train window that the device spends
in collective operations (all-gather, reduce-scatter, all-reduce,
all-to-all, collective-permute, with their asynchronous ``-start`` and
``-done`` halves): their device self time from ``xprof``'s ``hlo_stats``,
as a mean over the chips, the way :func:`bench.spans.scope_seconds` reads
a kernel scope's.

On a TPU v5e trace of the fsdp_tp train step the collectives carry the
categories ``all-reduce``, ``all-reduce-scatter fusion``, ``all-gather``,
``all-to-all`` and ``collective-permute-start``/``-done``; the weights'
FSDP all-gathers are ``custom fusion`` rows named ``async-collective-start``
and ``async-collective-done``. The matrix products that consume a gathered
weight (``async_collective_fusion``, category ``convolution fusion``) are
compute, and are not counted."""
import json
import re

from bench import spans

#: an operation is a collective where its category, its name or its opcode
#: starts with one of these
KINDS = re.compile(r"(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute"
                   r"|async-collective-(start|done))")
#: the opcode of an HLO instruction's text: ``%name = <shape> opcode(...)``
OPCODE = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([\w\-]+)\(")


def is_collective(category: str, name: str, text: str) -> bool:
    opcode = OPCODE.search(text or "")
    return any(KINDS.match(s or "") for s in
               (category, name, opcode.group(1) if opcode else ""))


def collective_seconds(table: dict, n_chips: int):
    """Seconds of collective self time a chip in an ``hlo_stats`` table, or
    ``None`` where it holds none."""
    cols = [c["id"] for c in table["cols"]]
    at = {k: cols.index(k) for k in ("category", "hlo_op_name",
                                     "hlo_op_expression", "total_self_time")}
    micros = [row["c"][at["total_self_time"]]["v"] for row in table["rows"]
              if is_collective(*(row["c"][at[k]]["v"] for k in
                                 ("category", "hlo_op_name", "hlo_op_expression")))]
    return sum(micros) / 1e6 / n_chips if micros else None


def read(ctx):
    s, path = ctx["trace"], spans.trace_file(ctx["cell"].run_dir)
    if not s or s["window_s"] <= 0 or path is None:
        return None
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return None
    data, _ = raw_to_tool_data.xspace_to_tool_data([str(path)], "hlo_stats", {})
    if not data:
        return None
    seconds = collective_seconds(json.loads(data), ctx["n_chips"])
    return None if seconds is None else 100.0 * seconds / s["window_s"]
