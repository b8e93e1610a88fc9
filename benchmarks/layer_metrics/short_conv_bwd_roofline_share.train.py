"""Trace: the gated short convolutions' BACKWARD pass's share of its
roofline, in percent: the least time for the backward half of
flops_lfm2_moe.short_conv_step (every convolution layer reads 4C and writes
3C a token: bytes bound it) over the backward seconds under the scope
`mx.sconv.conv`. The forward pass has no share of its own: the compiler
fuses it into the product before it, so its seconds are `mx.sconv`'s and it
moves no bytes of its own (PERF.md, PR 32); whatever forward seconds the
scope holds are left out with the forward's bytes. The rerun of a
rematerialised stage is filed as backward (reduce/op_scopes.py): its seconds
would count and its bytes would not (recomputation is in the time and not
in the operations). Nothing here depends on the reading: bytes counted too
high, or seconds filed elsewhere, read above 100."""
import flops_lfm2_moe
from reduce import op_scopes

SCOPE = "mx.sconv.conv"


def compute(ctx):
    scopes = op_scopes.of_run(ctx)
    row = scopes.self_s.get(SCOPE) if scopes else None
    if not row or not row["b"] or "sequences_per_step" not in ctx.host:
        return None
    tokens = ctx.host["sequences_per_step"] * ctx.config["sequence_length"]
    _, (flops, nbytes) = flops_lfm2_moe.short_conv_step(ctx.config, tokens)
    least = op_scopes.traced_steps(ctx) * max(
        flops / ctx.peaks["bf16_flops_per_s"],
        nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / row["b"]
