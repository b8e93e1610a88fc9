"""Seconds of the set-up window under no span of the program's own: before
its import (`process.start`: the interpreter, `jax`'s import and backend
start) and around its spans (the harness's draws, pools and model
building). Falls only when a span is added or the caller does less."""
from reduce import setup_spans


def compute(ctx):
    return setup_spans.metric(ctx, "setup_unattributed_s")
