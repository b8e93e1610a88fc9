"""Trace: the KDA core's share of its roofline, forward and backward, in
percent: the least time for flops_kimi_linear.kda_core_step's operations
and bytes over the time under the scope `mx.kda.core`."""
import flops_kimi_linear
from reduce import op_scopes


def compute(ctx):
    if "sequences_per_step" not in ctx.host:
        return None
    tokens = ctx.host["sequences_per_step"] * ctx.config["sequence_length"]
    return op_scopes.roofline_share(
        ctx, "mx.kda.core",
        *flops_kimi_linear.kda_core_step(ctx.config, tokens))
