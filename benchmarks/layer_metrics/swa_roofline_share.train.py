"""Trace: the windowed attention cores' share of their roofline, forward
and backward, in percent: the least time for the table's
`window_core_step` (every windowed layer's core, the keys a query sees
min(i + 1, window), k and v counted once a group) over the time under the
scope `mx.flash_attention.window` (the windowed flash kernels and XLA's
work around them; a backward that recomputes is in the time, not in the
operations). Also prints the program's attention counters as one
`attention_calls` line; a run that traced no windowed call reads
nothing."""
import op_tables
from common import emit
from reduce import op_scopes

COUNTERS = ("window_attention_calls_total", "gqa_attention_calls_total",
            "flash_bwd_kernel_calls_total", "attention_dense_fallback_total")


def compute(ctx):
    from mxnet_tpu.telemetry import registry
    counts = {}
    for name in COUNTERS:
        counter = registry.get_registry().get(name)
        counts[name] = counter.value() if counter is not None else 0
    emit("attention_calls", **counts)
    table = op_tables.table(ctx.config)
    tokens = op_tables.tokens_per_step(ctx)
    if table is None or tokens is None or not counts[COUNTERS[0]]:
        return None
    return op_scopes.roofline_share(
        ctx, "mx.flash_attention.window",
        *table.window_core_step(ctx.config, tokens))
