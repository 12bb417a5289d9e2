"""Median of the loop's ``data_wait`` span (the ``next()`` on the batch
iterator, timed inside ``run_train_epoch``) over the untraced part."""

import statistics

from layer_metrics.device_starved_share import untraced_call

UNIT = "ms"
SPAN = "data_wait"


def median_span_ms(records, span):
    spans = [r[span] for r in records if r[span] is not None]
    if not spans:
        return None
    return statistics.median(end - start for start, end in spans) / 1e6


def read(ctx):
    call = untraced_call(ctx)
    if call is None:
        return None
    return median_span_ms(call["records"], SPAN)
