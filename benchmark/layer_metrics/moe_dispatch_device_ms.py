"""Device time under ``tcdp.moe_dispatch`` per step (device trace): the
expert layers' router, top-k choice, sort, row gathers and scatter-add
combine, forward and backward: the latency- and memory-bound part of an expert
layer.  A program without the scope reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("moe_dispatch",))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
