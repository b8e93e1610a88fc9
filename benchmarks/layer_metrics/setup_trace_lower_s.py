"""Self seconds of the `compile.trace` and `compile.lower` spans in the
set-up window that are not under `devstats.extract`: Python tracing and
lowering to MLIR, which no cache saves a warm start."""
from reduce import setup_spans


def compute(ctx):
    return setup_spans.metric(ctx, "setup_trace_lower_s")
