"""Median of the program's `mx.feed.stage` spans that start in the traced
window (feeder thread: pull K batches, stack them, commit them to the
devices), in milliseconds: compare with the device time of a dispatch."""
from reduce import program_spans


def compute(ctx):
    return program_spans.feeder_median_ms(ctx, "mx.feed.stage")
