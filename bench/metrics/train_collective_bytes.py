"""Bytes a step that each chip's collectives move, in GB: the program's
``STEP_COLLECTIVES`` (``repro.train.trainer``), read from the compiled
step's per-device HLO when the loop compiled it on a mesh, summed over the
kinds (all-gather, reduce-scatter, all-reduce, ...)."""


def read(ctx):
    from repro.train import trainer

    totals = getattr(trainer, "STEP_COLLECTIVES", None)
    if not totals:
        return None
    return sum(totals.values()) / 1e9
