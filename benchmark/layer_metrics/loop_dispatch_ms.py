"""Median of the loop's ``dispatch`` span (the ``train_step`` call alone) over
the calls made while the device queue had room: fewer than 8 steps dispatched
and not yet completed when the call began, by the completion stamps.  With the
queue full the runtime holds the call until a slot frees, which measures the
device (``dispatch_ms_p50`` takes a window's first 16 calls for that reason)."""

from layer_metrics.device_starved_share import untraced_call
from layer_metrics.loop_data_wait_ms import median_span_ms

UNIT = "ms"
SPAN = "dispatch"
OUTSTANDING_BELOW = 8


def read(ctx):
    call = untraced_call(ctx)
    if call is None:
        return None
    free, pending = [], []     # pending: stamps of the steps dispatched so far
    for r in call["records"]:
        if r[SPAN] is None:
            continue
        began = r[SPAN][0]
        pending = [done for done in pending if done is None or done > began]
        if len(pending) < OUTSTANDING_BELOW:
            free.append(r)
        pending.append(r["done"])
    return median_span_ms(free, SPAN)
