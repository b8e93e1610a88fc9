"""Model FLOP/s utilization: flops.py's operations per trained sample times
the samples per second per chip of this run's window, over the bf16 peak of
peaks.json, in percent. End to end, not a kernel's roofline share."""
import flops


def compute(ctx):
    rate = ctx.end_to_end.get("train_rate")
    if rate is None:
        return None
    return 100.0 * flops.train_flops(ctx.config) * rate \
        / ctx.peaks["bf16_flops_per_s"]
