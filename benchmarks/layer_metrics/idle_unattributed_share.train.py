"""Trace: share of the traced window in which device 0 ran nothing and the
training loop's thread was in no `mx.*` span at all, in percent: what the
program's spans do not name yet."""
from reduce import program_spans


def compute(ctx):
    return program_spans.idle_share(ctx, program_spans.UNATTRIBUTED)
