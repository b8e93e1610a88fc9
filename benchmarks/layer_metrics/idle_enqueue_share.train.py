"""Trace: share of the traced window in which device 0 ran nothing while
the training loop was inside `trainer.step_k` (the program's
`mx.step.enqueue` span: the enqueue of the fused scan, which returns before
the device ends), in percent."""
from reduce import program_spans


def compute(ctx):
    return program_spans.idle_share(ctx, "mx.step.enqueue")
