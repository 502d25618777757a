"""Share of the train loop's steps whose batch was built ahead, while the
device ran the previous step, out of every step the process ran, set-up
included: the program's ``FEED_TOTALS`` (``repro.train.trainer``)."""


def read(ctx):
    from repro.train import trainer

    totals = getattr(trainer, "FEED_TOTALS", None)
    if not totals:
        return None
    steps = totals["ahead"] + totals["inline"]
    return 100.0 * totals["ahead"] / steps if steps else None
