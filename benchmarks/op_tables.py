"""Which table of operations and bytes a configuration's `model` has: the
module beside this file that computes them from the configuration's shapes
(its functions are the table's own), or None for a model that has none
here. The readers of `mfu.train` and the attention roofline shares find it
through `table`, so one set of names serves every model that is given a
table."""
import importlib

TABLES = {"afmoe": "flops_afmoe"}


def table(config):
    name = TABLES.get(config.get("model"))
    return importlib.import_module(name) if name else None


def tokens_per_step(ctx):
    """Tokens of one training step, or None where the run says nothing."""
    if "sequences_per_step" not in ctx.host:
        return None
    return ctx.host["sequences_per_step"] * ctx.config["sequence_length"]
