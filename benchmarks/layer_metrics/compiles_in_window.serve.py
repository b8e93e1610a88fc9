"""XLA compile requests (jax.monitoring) inside the window; must be 0."""


def compute(ctx):
    return ctx.host.get("compiles_in_window")
