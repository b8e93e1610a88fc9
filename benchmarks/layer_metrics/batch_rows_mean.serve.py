"""Rows per coalesced batch in the window: batched_rows / batches of the
batcher's metrics snapshot. A count."""


def compute(ctx):
    if not ctx.host.get("batches"):
        return None
    return ctx.host["batched_rows"] / ctx.host["batches"]
