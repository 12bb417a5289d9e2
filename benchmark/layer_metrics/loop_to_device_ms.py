"""Median of the loop's ``to_device`` span (the ``jnp.asarray`` of the batch:
the host-to-device copy, apart from the step's call) over the untraced part."""

from layer_metrics.device_starved_share import untraced_call
from layer_metrics.loop_data_wait_ms import median_span_ms

UNIT = "ms"
SPAN = "to_device"


def read(ctx):
    call = untraced_call(ctx)
    if call is None:
        return None
    return median_span_ms(call["records"], SPAN)
