"""XLA backend-compile seconds (jax.monitoring) during set-up; a run that
finds every program in the cache still pays the loading."""


def compute(ctx):
    return ctx.host.get("setup_compile_s")
