"""Median host time inside the ``train_step`` call while the device queue has
room (benchmark's host span).  The runtime lets the host run some thirty steps
ahead and then holds each call until a slot frees, which measures the device,
not the dispatch; a window opens on a drained queue, so its first 16 calls are
the ones that did not wait."""

import statistics

UNIT = "ms"
UNBLOCKED_CALLS = 16


def read(ctx):
    calls = ctx.dispatch_s[:UNBLOCKED_CALLS]
    if len(calls) < UNBLOCKED_CALLS:
        return None
    return 1e3 * statistics.median(calls)
