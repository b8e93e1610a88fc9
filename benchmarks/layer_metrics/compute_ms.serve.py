"""Median time of one `engine.infer` call in the window, by the benchmark's
clock around it (runners/serve_open_loop.py::TimedEngine): host padding, the
copy in, the plan, the answer on the host. Not the program's `serve.compute`
span, which ends at the dispatch of the plan."""
from common import quantile


def compute(ctx):
    calls = ctx.host.get("engine_infer_ms")
    return quantile(calls, 0.5) if calls else None
