"""Trace, device 0: share of busy time in operations under the windowed
layers' attention scopes, `table.WINDOW_SCOPES` of the configuration's
table (`op_tables.table`): `mx.swa` (the operator's products, head norms
and, under `mx.swa.rope` and `mx.swa.gate`, the rotation and the output
gate) and `mx.flash_attention.window` (its flash kernels), forward and
backward, in percent. A program without `mx.swa` reads nothing."""
import op_tables
from reduce import op_scopes


def compute(ctx):
    table = op_tables.table(ctx.config)
    if table is None or not getattr(table, "WINDOW_SCOPES", None):
        return None
    mixer, kernels = table.WINDOW_SCOPES
    share = op_scopes.share(ctx, mixer)
    if not share:
        return None
    return share + (op_scopes.share(ctx, kernels) or 0.0)
