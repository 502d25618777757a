"""Model FLOP utilization of the train step: model FLOPs per token times
tokens per second, over the chips' bf16 peak. The rate is taken over the
window's steps after the trace stopped, so the profiler's cost is left out."""
from bench import flops, harness


def read(ctx):
    c = ctx["counters"]
    ends = c.get("step_ends") or []
    start = c.get("trace_stopped") or c.get("t0")
    after = [t for t in ends if t > start]
    if len(after) < 2:
        start, after = c.get("t0"), ends
    if not after:
        return None
    rate = len(after) * c["tokens_per_step"] / (after[-1] - start)
    cell = ctx["cell"]
    per_token = flops.train_flops_per_token(cell.config, cell.traffic["seq_len"])
    peak = harness.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * rate / (ctx["n_chips"] * peak)
