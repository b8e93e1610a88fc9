"""Median host time between successive batch_end_callbacks in the window
(the fused loop syncs on each dispatch's outputs before the callback)."""
from common import quantile


def compute(ctx):
    gaps = ctx.host.get("dispatch_gaps_ms")
    return quantile(gaps, 0.5) if gaps else None
