"""Seconds of the program's `import.mxnet_tpu` span (first line to last of
`mxnet_tpu/__init__.py`), from its flight recorder. Nothing where the
ring's records cannot be put on a timeline."""
from reduce import setup_spans


def compute(ctx):
    return setup_spans.metric(ctx, "setup_import_s")
