"""Trace, device 0: share of busy time in operations under the scopes
`mx.mla` (the latent-attention mixer's projections and norm) and
`mx.flash_attention` (its three flash kernels, which the attention op names
itself: the innermost scope is the one an operation is filed under),
forward and backward, in percent."""
from reduce import op_scopes


def compute(ctx):
    mixer = op_scopes.share(ctx, "mx.mla")
    kernels = op_scopes.share(ctx, "mx.flash_attention")
    if mixer is None and kernels is None:
        return None
    return (mixer or 0.0) + (kernels or 0.0)
