"""Median host time in ``next(batches)`` per step (benchmark's host span)."""

import statistics

UNIT = "ms"


def read(ctx):
    if ctx.feed_kind != "loader" or not ctx.wait_s:
        return None
    return 1e3 * statistics.median(ctx.wait_s)
