"""Trace: the full (unwindowed) attention cores' share of their roofline,
forward and backward, in percent: the least time for the table's
`full_core_step` (every full layer's causal core, k and v counted once a
group) over the seconds under `mx.flash_attention` less those under
`mx.flash_attention.window`, which lies inside it (recomputation in the
time, not in the operations)."""
import op_tables
from reduce import op_scopes


def compute(ctx):
    table = op_tables.table(ctx.config)
    tokens = op_tables.tokens_per_step(ctx)
    scopes = op_scopes.of_run(ctx)
    if table is None or tokens is None or not scopes:
        return None
    seconds = scopes.seconds("mx.flash_attention") - \
        scopes.seconds("mx.flash_attention.window")
    if seconds <= 0:
        return None
    flops, nbytes = table.full_core_step(ctx.config, tokens)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * op_scopes.traced_steps(ctx) / seconds
