"""How far the tuner's own per-call time of its chosen tile lies from the
benchmark's re-timing of that tile, as a share of the re-timing; the mean
over the jobs completed in the window."""


def read(ctx):
    errors = ctx["counters"].get("objective_errors")
    if not errors:
        return None
    return 100.0 * sum(errors) / len(errors)
