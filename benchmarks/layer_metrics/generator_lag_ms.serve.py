"""How late the load generator ran: actual send minus due time, 95th
percentile over the window. A starved generator is not a fast server."""
from common import quantile


def compute(ctx):
    lag = ctx.host.get("generator_lag_ms")
    return quantile(lag, 0.95) if lag else None
