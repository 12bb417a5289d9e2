"""Device time of the sync engine's scopes (ef, compress, route, reduce,
return) per step, read directly from the device trace: no subtraction of two
step times."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps or not ctx.compressed:
        return None
    return 1e3 * ctx.reduce.scope_seconds(
        ctx.extract, ctx.reduce.SYNC_PHASES) / ctx.traced_steps
