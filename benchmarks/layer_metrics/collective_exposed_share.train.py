"""Trace, device 0: time in collective operations during which no other
operation runs there, as a share of the traced window, in percent."""


def compute(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace.devices[0].exposed_s("collective") \
        / ctx.trace.window_s
