"""Share of the traced stretch of the train window in which the device is
idle while the loop's ``train.sync`` span is open: the step's metrics read
back to the host."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, "train.sync")
