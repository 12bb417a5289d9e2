"""Device time of the expert layers per step, forward and backward (device
trace): the operations whose innermost scope is ``tcdp.moe`` (latent
projections, shared expert) or, nested in it, ``tcdp.moe_dispatch`` (router,
choice, sort, gather, combine) or ``tcdp.experts`` (the grouped product).  A
program without the scopes reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract,
                                       ("moe", "moe_dispatch", "experts"))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
