"""95th percentile of the intervals between consecutive steps' completion
stamps over the untraced part: the step-time tail, with no profiler and no
wait on the device in the loop.  None while a stamp is missing."""

import numpy as np

from layer_metrics.device_starved_share import untraced_call

UNIT = "ms"


def read(ctx):
    call = untraced_call(ctx)
    if call is None:
        return None
    done = [r["done"] for r in call["records"]]
    if len(done) < 2 or any(d is None for d in done):
        return None
    return float(np.percentile(np.diff(done), 95)) / 1e6
