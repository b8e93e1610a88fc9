"""Median of the program's `mx.feed.put` spans that start in the traced
window (feeder thread: `trainer.shard_inputs`, the `jax.device_put` of one
block to the mesh), in milliseconds."""
from reduce import program_spans


def compute(ctx):
    return program_spans.feeder_median_ms(ctx, "mx.feed.put")
