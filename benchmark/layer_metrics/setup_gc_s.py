"""Seconds of set-up inside a pass of Python's garbage collector, every
generation: the ring's totals when the untraced part's ``run_train_epoch``
call began (every pass adds to them, also the many too short for the ring).
The passes lie inside the other ``setup_*`` readers' seconds where they fell
in a trace, a lowering or a compile."""

from layer_metrics.device_starved_share import untraced_call

UNIT = "s"


def read(ctx):
    call = untraced_call(ctx)
    if call is None or "totals0" not in call:
        return None
    return call["totals0"].get("gc", (0, 0))[1] / 1e9
