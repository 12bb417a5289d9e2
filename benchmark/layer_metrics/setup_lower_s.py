"""Seconds of set-up in which the program lowered a jaxpr to an MLIR module:
the union of the ``lower`` events less what a ``trace`` event covers (a
function jitted inside a trace lowers inside it)."""

from layer_metrics.setup_trace_s import covered_s

UNIT = "s"


def read(ctx):
    return covered_s(ctx, ("lower",), ("trace",))
