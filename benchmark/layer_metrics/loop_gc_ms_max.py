"""Longest pass of Python's garbage collector that overlapped the untraced
part's ``run_train_epoch`` call, from the program's host-event ring.  The ring
keeps the passes of 1 ms or more: 0 says that none reached that."""

from layer_metrics.device_starved_share import untraced_call

UNIT = "ms"


def read(ctx):
    call = untraced_call(ctx)
    if call is None or "events" not in call:
        return None
    return max((end - start for kind, _, start, end in call["events"]
                if kind == "gc"), default=0) / 1e6
