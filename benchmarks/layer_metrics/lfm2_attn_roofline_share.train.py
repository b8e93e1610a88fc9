"""Trace: the grouped-query attention cores' share of their roofline,
forward and backward, in percent: the least time for
flops_lfm2_moe.gqa_core_step's operations and bytes (every attention
layer's core, k and v counted once a group) over the time under the scope
`mx.flash_attention` (the three flash kernels)."""
import flops_lfm2_moe
from reduce import op_scopes


def compute(ctx):
    if "sequences_per_step" not in ctx.host:
        return None
    tokens = ctx.host["sequences_per_step"] * ctx.config["sequence_length"]
    return op_scopes.roofline_share(
        ctx, "mx.flash_attention",
        *flops_lfm2_moe.gqa_core_step(ctx.config, tokens))
