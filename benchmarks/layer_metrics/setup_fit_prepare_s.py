"""Seconds in the four set-up steps of the fused fit (the program's
`fit.bind`, `fit.init_params`, `fit.trainer_init` and `fit.init_state`
spans, from its flight recorder: the profiler is not yet running then).
Nothing where the run never called `fit`."""
from reduce import program_spans


def compute(ctx):
    return program_spans.setup_fit_prepare_s()
