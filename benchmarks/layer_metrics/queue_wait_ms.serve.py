"""Median of the program's `serve.queue` spans (a request's time in the
batcher's queue, host perf_counter) over the traced window."""
from common import quantile


def compute(ctx):
    spans = ctx.host.get("spans_ms", {}).get("serve.queue")
    return quantile(spans, 0.5) if spans else None
