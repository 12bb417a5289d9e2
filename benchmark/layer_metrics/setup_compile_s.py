"""Seconds of set-up in which the backend compiled a program or the
persistent cache answered in its place: the union of the ``compile`` and
``cache_read`` events less what a ``trace`` or ``lower`` event covers (an
eager operation inside a trace compiles inside it).  Warm, these are the
cache's reads."""

from layer_metrics.setup_trace_s import covered_s

UNIT = "s"


def read(ctx):
    return covered_s(ctx, ("compile", "cache_read"), ("trace", "lower"))
