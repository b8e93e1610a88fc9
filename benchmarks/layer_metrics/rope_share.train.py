"""Trace, device 0: share of busy time in operations under the scope
`mx.mla.rope` (the rotary embedding of every query head's and of the shared
key's position dims), forward and backward, in percent. A program without
the scope reads nothing."""
from reduce import op_scopes


def compute(ctx):
    return op_scopes.share(ctx, "mx.mla.rope") or None
