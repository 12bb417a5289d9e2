"""Seconds of set-up in which the program traced a jitted function to a
jaxpr: the length of the union of the ``trace`` events (they nest: a function
traced inside another's trace raises an event of its own) that the program's
host-event ring (``obs.trace.HostEvents``) stamped before the window.

"Set-up" is where ``run.py`` stops ``setup_s``: every event that ended at or
before the begin of the untraced part's ``run_train_epoch`` call.  The four
``setup_*`` readers take their events here.  ``setup_trace_s``,
``setup_lower_s`` and ``setup_compile_s`` partition the covered time: each is
what its kind covers and no earlier kind does (interval subtraction of
stamped spans), so their sum is no more than ``setup_s``.
"""

from layer_metrics.device_starved_share import untraced_call

UNIT = "s"


def setup_spans(ctx):
    """``{kind: merged [start, end] ns}`` of the host events that ended at or
    before the untraced call's ``t0``.  None from a program that keeps no
    such ring, when the calls do not line up (``untraced_call``), and when
    the ring no longer holds every event the totals counted by then."""
    call = untraced_call(ctx)
    if call is None or "totals0" not in call:
        return None
    from tpu_compressed_dp.obs import trace

    t0 = call["t0"]
    spans = {}
    for kind, _, start, end in trace.process_timeline().host_events(None, t0):
        if end <= t0:
            spans.setdefault(kind, []).append([start, end])
    for kind, (count, _) in call["totals0"].items():
        if kind in ("trace", "lower", "compile", "cache_read") \
                and len(spans.get(kind, ())) < count:
            return None
    return {kind: ctx.reduce.union(held) for kind, held in spans.items()}


def covered_s(ctx, kinds, less=()):
    """Seconds of set-up that events of ``kinds`` cover and events of
    ``less`` do not."""
    spans = setup_spans(ctx)
    if spans is None:
        return None
    merged = lambda names: ctx.reduce.union(
        span for name in names for span in spans.get(name, ()))
    return ctx.reduce.length(
        ctx.reduce.subtract(merged(kinds), merged(less))) / 1e9


def read(ctx):
    return covered_s(ctx, ("trace",))
