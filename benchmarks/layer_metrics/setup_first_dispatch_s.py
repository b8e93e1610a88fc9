"""Seconds of the process's first `step.fused_dispatch` span (program load
or compile, devstats' extraction, the first run), from the program's flight
recorder. Nothing where the run never called `fit`."""
from reduce import program_spans


def compute(ctx):
    return program_spans.setup_first_dispatch_s()
