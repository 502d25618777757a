"""Share of the traced stretch of the train window in which the device is
idle while the loop's ``train.feed`` span is open: the batch built on the
host and put on the device."""
from bench import spans


def read(ctx):
    return spans.idle_share(ctx, "train.feed")
