"""Share of the tune window spent compiling trials in the back end, as
each trial's ``meta["compile_seconds"]`` says: XLA's and Mosaic's compile
of the lowered module.

The driver sums only ``build_seconds`` over the trials that ended inside
the window; the program's process totals give the compile's share of
the build, over every trial the process ran."""
KEY = "compile_seconds"


def read(ctx):
    c = ctx["counters"]
    if not c.get("attempted"):
        return None
    from repro.tuning import evaluator

    totals = getattr(evaluator, "PHASE_TOTALS", None)
    if not totals or totals["build_seconds"] <= 0:
        return None
    share = totals[KEY] / totals["build_seconds"]
    return 100.0 * share * c["build_s"] / c["window_s"]
