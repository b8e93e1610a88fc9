"""Model FLOP/s utilization of a training step: the operations one trained
sequence needs (the configuration's table, `op_tables.table`: forward +
backward, nothing recomputed) times the sequences per second per chip of
this run's window, over the bf16 peak of peaks.json, in percent. The share
of the whole step's peak, not a kernel's roofline share. A model without a
table reads nothing."""
import op_tables


def compute(ctx):
    table = op_tables.table(ctx.config)
    rate = ctx.end_to_end.get("train_rate")
    if table is None or rate is None:
        return None
    return 100.0 * table.train_flops_per_sequence(ctx.config) * rate \
        / ctx.peaks["bf16_flops_per_s"]
