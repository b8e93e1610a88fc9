"""Model FLOP/s utilization of a `deepseek_v3` step: the operations one
trained sequence needs (flops_deepseek_v3.py: forward + backward, nothing
recomputed) times the sequences per second per chip of this run's window,
over the bf16 peak of peaks.json, in percent. The share of the whole step's
peak, not a kernel's roofline share."""
import flops_deepseek_v3


def compute(ctx):
    rate = ctx.end_to_end.get("train_rate")
    if rate is None:
        return None
    return 100.0 * flops_deepseek_v3.train_flops_per_sequence(ctx.config) \
        * rate / ctx.peaks["bf16_flops_per_s"]
