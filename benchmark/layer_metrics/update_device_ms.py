"""Device time under the ``tcdp.update`` scope per step (device trace)."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    return 1e3 * ctx.reduce.scope_seconds(ctx.extract, ("update",)) / ctx.traced_steps
