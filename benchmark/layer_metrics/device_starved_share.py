"""Share of the window's wall time in which the device had no step queued,
from the program's own records: each step's completion stamp against the end
of the next step's dispatch span (``starved`` of ``obs.trace.StepTimeline``),
summed over the untraced part of a ``--trace 1`` run with the first step left
out (the device is idle while the first batch is made), over the wall of that
``run_train_epoch`` call.  No profiler is running in that part.

The readers of source ``program_span`` take their records here:
``untraced_call`` is the one way to the program's timeline.
"""

UNIT = "%"


def untraced_call(ctx):
    """The ``run_train_epoch`` call before the last, as the program's
    process-wide timeline recorded it: ``{"t0", "t1", "fetch", "steps",
    "records"}``, nanoseconds.  Only when the last call counted the traced
    part's steps and the one before it the untraced part's, every record is
    still held and the call was closed; else None, and never another window.
    None too from a program that keeps no such timeline."""
    try:
        from tpu_compressed_dp.obs import trace
    except ImportError:
        return None
    timeline = getattr(trace, "process_timeline", None)
    if timeline is None or not ctx.traced_steps:
        return None
    calls = timeline().calls()
    if len(calls) < 2:
        return None
    call, traced = calls[-2], calls[-1]
    if traced["steps"] != ctx.traced_steps or call["steps"] != len(ctx.dispatch_s):
        return None
    if len(call["records"]) != call["steps"] or call["t1"] is None:
        return None
    return call


def share_of_wall(call, per_step) -> float:
    """100 x the sum of ``per_step(record)`` nanoseconds over the call's
    steps but the first, over the call's wall; None while a stamp is missing."""
    parts = [per_step(r) for r in call["records"][1:]]
    if not parts or any(p is None for p in parts):
        return None
    return 100.0 * sum(parts) / (call["t1"] - call["t0"])


def read(ctx):
    call = untraced_call(ctx)
    if call is None:
        return None
    return share_of_wall(call, lambda r: r["starved"])
