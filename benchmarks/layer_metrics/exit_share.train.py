"""Trace, device 0: share of busy time in operations after the passes of
a looped model (`flops_ouro.EXIT_SCOPES`: `mx.lm_head`, each pass's final
norm and the head's cross-entropy, and `mx.exit_gate`, the gates and the
objective over the passes), forward and backward, in percent. A program
without `mx.exit_gate` reads nothing."""
import flops_ouro
from reduce import op_scopes


def compute(ctx):
    if not op_scopes.share(ctx, "mx.exit_gate"):
        return None
    return sum(op_scopes.share(ctx, s) or 0.0 for s in flops_ouro.EXIT_SCOPES)
