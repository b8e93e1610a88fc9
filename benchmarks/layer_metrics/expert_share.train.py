"""Trace, device 0: share of busy time in operations under the scopes
`mx.moe.*` (router, the held experts' gather, scatter and grouped products,
whose `ragged-dot` kernels reduce/op_scopes.py files under
`mx.moe.experts.matmul`, the dense path where it is taken, the shared
expert), forward and backward, in percent."""
from reduce import op_scopes


def compute(ctx):
    return op_scopes.share(ctx, "mx.moe")
