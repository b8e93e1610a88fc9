"""Trace, device 0: share of busy time in operations under the scope
`mx.kda` (the KDA mixers: projections, convolutions, decay, the chunked
delta rule, output gate), forward and backward, in percent."""
from reduce import op_scopes


def compute(ctx):
    return op_scopes.share(ctx, "mx.kda")
