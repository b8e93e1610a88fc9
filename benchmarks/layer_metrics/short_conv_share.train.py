"""Trace, device 0: share of busy time in operations under the scope
`mx.sconv` (the gated short-convolution operators: their two products and,
under `mx.sconv.conv`, the gated pass between them), forward and backward,
in percent. A program without the scope reads nothing."""
from reduce import op_scopes


def compute(ctx):
    return op_scopes.share(ctx, "mx.sconv") or None
