"""Device time under the ``tcdp.head_xent`` scope per step (device trace): the
head's products and the cross-entropies of every pass, forward and backward.
A program without the scope reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("head_xent",))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
