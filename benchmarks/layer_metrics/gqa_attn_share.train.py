"""Trace, device 0: share of busy time in operations under the scopes
`mx.gqa` (the grouped-query attention operator's four products, its two
head norms and, under `mx.gqa.rope`, the rotation) and
`mx.flash_attention` (its three flash kernels, which the attention op names
itself: the innermost scope is the one an operation is filed under),
forward and backward, in percent. A program without `mx.gqa` reads
nothing."""
from reduce import op_scopes


def compute(ctx):
    mixer = op_scopes.share(ctx, "mx.gqa")
    if not mixer:
        return None
    return mixer + (op_scopes.share(ctx, "mx.flash_attention") or 0.0)
