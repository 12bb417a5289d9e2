"""1 - busy / window of the traced window (device trace, mean over chips)."""

UNIT = "%"


def read(ctx):
    if ctx.extract is None:
        return None
    return 100.0 * (1.0 - ctx.reduce.busy_seconds(ctx.extract)
                    / ctx.reduce.window_seconds(ctx.extract))
