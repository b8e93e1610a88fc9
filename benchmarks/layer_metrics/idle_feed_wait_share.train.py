"""Trace: share of the traced window in which device 0 ran nothing while
the training loop was blocked in `DeviceFeed.__next__` (the program's
`mx.feed.wait` span on the loop thread), in percent."""
from reduce import program_spans


def compute(ctx):
    return program_spans.idle_share(ctx, "mx.feed.wait")
