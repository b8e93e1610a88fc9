"""Model FLOP/s utilization of an `lfm2_moe` step: the operations one
trained sequence needs (flops_lfm2_moe.py: forward + backward, nothing
recomputed) times the sequences per second per chip of this run's window,
over the bf16 peak of peaks.json, in percent. The share of the whole step's
peak, not a kernel's roofline share."""
import flops_lfm2_moe


def compute(ctx):
    rate = ctx.end_to_end.get("train_rate")
    if rate is None:
        return None
    return 100.0 * flops_lfm2_moe.train_flops_per_sequence(ctx.config) \
        * rate / ctx.peaks["bf16_flops_per_s"]
