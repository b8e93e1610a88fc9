"""Trace, device 0: convolution fusions' share of the device's busy time, in
percent (self times, so an enclosing while counts for nothing)."""


def compute(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace.category_share_of_busy("convolution fusion")
