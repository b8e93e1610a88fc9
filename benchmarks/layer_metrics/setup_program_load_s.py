"""Self seconds of the `compile.backend` spans in the set-up window that
are not under `devstats.extract`: retrieval, deserialisation and load on a
cache hit, XLA's compile on a miss. `setup_compile_s` counts extraction's
too."""
from reduce import setup_spans


def compute(ctx):
    return setup_spans.metric(ctx, "setup_program_load_s")
