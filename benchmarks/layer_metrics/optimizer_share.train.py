"""Trace, device 0: share of busy time in operations under the scope
`mx.optimizer` (the fused update of every parameter from its gradient and
state), in percent."""
from reduce import op_scopes


def compute(ctx):
    return op_scopes.share(ctx, "mx.optimizer")
