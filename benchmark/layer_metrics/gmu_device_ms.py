"""Device time of the Gated Memory Units per step, forward and backward
(device trace): the operations whose innermost scope is ``tcdp.gmu`` (the
gate's projection, ``m * silu(.)`` on the handed-on scan output, the output
projection).  A program without the scope reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("gmu",))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
