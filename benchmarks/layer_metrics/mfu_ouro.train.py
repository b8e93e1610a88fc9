"""Model FLOP/s utilization of an `ouro` step: the operations one trained
sequence needs (flops_ouro.py: forward + backward over every pass, nothing
recomputed) times the sequences per second per chip of this run's window,
over the bf16 peak of peaks.json, in percent. The share of the whole
step's peak, not a kernel's roofline share."""
import flops_ouro


def compute(ctx):
    rate = ctx.end_to_end.get("train_rate")
    if rate is None or ctx.config.get("model") != "ouro":
        return None
    return 100.0 * flops_ouro.train_flops_per_sequence(ctx.config) \
        * rate / ctx.peaks["bf16_flops_per_s"]
