"""The part of ``device_starved_share`` that fell under the loop's
``data_wait`` spans (the ``next()`` on the batch iterator): the device waited
for the input pipeline.  Same steps, same wall."""

from layer_metrics.device_starved_share import share_of_wall, untraced_call

UNIT = "%"


def under_data_wait(record):
    if record["starved"] is None:
        return None
    return (record["starved_by"] or {}).get("data_wait", 0)


def read(ctx):
    call = untraced_call(ctx)
    if call is None:
        return None
    return share_of_wall(call, under_data_wait)
