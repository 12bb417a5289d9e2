"""Device time of the routed experts' grouped product per step, forward and
backward (device trace): the operations whose innermost scope is
``tcdp.experts`` (the products of the loop over occupied tiles, the weights'
casts; the loop's gathers and scatter-adds are under ``tcdp.moe_dispatch``).
A program without the scope reads nothing."""

UNIT = "ms"


def seconds_under_experts(ctx) -> float:
    if ctx.extract is None or not ctx.traced_steps:
        return 0.0
    return ctx.reduce.scope_seconds(ctx.extract, ("experts",))


def read(ctx):
    seconds = seconds_under_experts(ctx)
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
