"""Trace, device 0: the Pallas KDA kernels' share of the time under the
scope `mx.kda.core`, forward and backward, in percent: seconds of the
operations named `mx_kda_*` over the scope's self seconds. 100 less this
is what XLA still does around the kernels (layout copies, padding, the
transposes of beta); a program without the kernels reads 0, a trace
without the scope nothing."""
import os
import re

from reduce import op_scopes

KERNEL = re.compile(r"mx_kda_")
SCOPE = "mx.kda.core"


def share(ops, window):
    """Percent, from `op_scopes.read_ops`' rows [scope, start_s,
    duration_s, way, name]; None where nothing ran under the scope."""
    under = op_scopes.Scopes(ops, window).seconds(SCOPE)
    if not under:
        return None
    lo, hi = window
    kernels = sum(e[2] for e in ops if len(e) > 4 and KERNEL.search(e[4])
                  and e[1] + e[2] > lo and e[1] < hi)
    return 100.0 * kernels / under


def compute(ctx):
    if not ctx.trace:
        return None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return share(op_scopes.read_ops(os.path.join(
        here, ".bench_scratch", ctx.cell["name"], "profile")),
        tuple(ctx.trace.window))
