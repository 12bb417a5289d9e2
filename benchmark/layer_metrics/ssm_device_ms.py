"""Device time of the Mamba-2 mixers per step, forward and backward (device
trace): the operations whose innermost scope is ``tcdp.ssm`` (projections,
gate, group-wise norm) or, nested in it, ``tcdp.ssd`` (convolution and scan).
A program without the scopes reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("ssm", "ssd"))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
