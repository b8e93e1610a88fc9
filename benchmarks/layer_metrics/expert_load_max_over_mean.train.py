"""Counter: the largest token count a held expert saw in one layer of one
step of the window over the mean count (the program's `moe_expert_load_max`
and `_mean` gauges): 1 is a balanced router."""


def compute(ctx):
    moe = ctx.host.get("moe")
    if not moe or not moe.get("expert_load_mean"):
        return None
    return moe["expert_load_max"] / moe["expert_load_mean"]
