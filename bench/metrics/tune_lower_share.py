"""Share of the tune window spent lowering trials, as each trial's
``meta["lower_seconds"]`` says: JAX's jaxpr-to-MLIR conversion, the Pallas
kernel's lowering to Mosaic included.

The driver sums only ``build_seconds`` over the trials that ended inside
the window; the program's process totals give the lowering's share of
the build, over every trial the process ran."""
KEY = "lower_seconds"


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
