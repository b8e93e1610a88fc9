"""Seconds of the first `step.metric_update` (the device's first K steps
and the fetch of their outputs), less the `compile.*` spans under it."""
from reduce import setup_spans


def compute(ctx):
    return setup_spans.metric(ctx, "setup_first_run_s")
