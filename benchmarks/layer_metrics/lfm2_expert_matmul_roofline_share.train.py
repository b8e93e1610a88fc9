"""Trace: the held experts' grouped products' share of their roofline,
forward and backward, in percent: the least time for
flops_lfm2_moe.expert_matmul_step's operations and bytes, for the pairs
that went through the grouped products in the traced steps (the step's own
statistics: a layer-step on the dense path multiplies none), over the time
of the `ragged-dot` kernels, which reduce/op_scopes.py files under
`mx.moe.experts.matmul`."""
import flops_lfm2_moe
from reduce import op_scopes


def compute(ctx):
    pairs = ctx.host.get("moe", {}).get("traced_grouped_pairs")
    if not pairs:
        return None
    return op_scopes.roofline_share(
        ctx, "mx.moe.experts.matmul", *flops_lfm2_moe.expert_matmul_step(
            ctx.config, pairs / op_scopes.traced_steps(ctx)))
