"""Trace, device 0: 1 - union of the operations' intervals over the traced
window, in percent."""


def compute(ctx):
    return 100.0 * ctx.trace.idle_share(0) if ctx.trace else None
