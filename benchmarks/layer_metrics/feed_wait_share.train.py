"""Share of the window the training loop spent blocked on DeviceFeed
(pipeline.stats()["feed_wait_us"] over the window), in percent."""


def compute(ctx):
    if "feed_wait_s" not in ctx.host:
        return None
    return 100.0 * ctx.host["feed_wait_s"] / ctx.host["window_s"]
