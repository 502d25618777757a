"""Share of the tune window spent outside the evaluator's calls: the
engine's asks and tells, the scheduler, and the job's own set-up and
record, as the benchmark's wrapper around each evaluator call measures."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("attempted"):
        return None
    return 100.0 * (c["window_s"] - c["evaluator_s"]) / c["window_s"]
