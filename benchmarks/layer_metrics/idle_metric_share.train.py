"""Trace: share of the traced window in which device 0 ran nothing while
the training loop updated the metric (the program's `mx.step.metric_update`
span: it fetches the dispatch's outputs, so it also waits for the device),
in percent."""
from reduce import program_spans


def compute(ctx):
    return program_spans.idle_share(ctx, "mx.step.metric_update")
