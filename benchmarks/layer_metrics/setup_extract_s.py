"""Seconds of the `devstats.extract` spans in the set-up window, whole:
devstats' own trace, lowering and compile-or-load of the step program, to
read its cost and memory analysis (ROADMAP S3 (a))."""
from reduce import setup_spans


def compute(ctx):
    return setup_spans.metric(ctx, "setup_extract_s")
