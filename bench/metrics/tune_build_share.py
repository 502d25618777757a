"""Share of the tune window spent building trials: tracing, lowering,
compiling and warming up, as each trial's ``meta["build_seconds"]`` says."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("attempted"):
        return None
    return 100.0 * c["build_s"] / c["window_s"]
