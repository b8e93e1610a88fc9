"""Counter: the mixture layer-steps of the window that took the dense path
(more pairs on the held experts than the grouped products have rows: the
program's `moe_dense_fallback_total`) over all its layer-steps, in percent.
Such a layer-step costs every held expert on every token, so a window that
reads high timed the dense path."""


def compute(ctx):
    moe = ctx.host.get("moe")
    if not moe or not moe.get("layer_steps"):
        return None
    return 100.0 * moe["moe_dense_fallback_total"] / moe["layer_steps"]
