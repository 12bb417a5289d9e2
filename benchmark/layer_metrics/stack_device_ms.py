"""Device time of the decoder's passes over its layers per step, forward and
backward (device trace): the operations whose innermost scope is
``tcdp.stack`` or, nested in it, ``tcdp.attn``.  The reduction keeps an
operation's innermost scope only, so the two are summed here; a program
without the scopes reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("stack", "attn"))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
