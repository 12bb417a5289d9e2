"""Device time of the dense gated feed-forwards per step, forward and backward
(device trace): the operations whose innermost scope is ``tcdp.mlp`` (the
gate-and-up product, the gate, the down product, of every layer that has the
scope).  A program without the scope reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("mlp",))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
