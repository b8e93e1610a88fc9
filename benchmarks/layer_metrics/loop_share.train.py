"""Trace, device 0: share of busy time in operations inside the loop of a
looped model (`flops_ouro.LOOP_SCOPES`: `mx.loop`, which holds the passes'
norms and residual adds, and the scopes inside it that the layers name,
their attention with its rotation and flash kernels and their MLP),
forward and backward, in percent. A program without `mx.loop` reads
nothing."""
import flops_ouro
from reduce import op_scopes


def compute(ctx):
    if not op_scopes.share(ctx, "mx.loop"):
        return None
    return sum(op_scopes.share(ctx, s) or 0.0 for s in flops_ouro.LOOP_SCOPES)
