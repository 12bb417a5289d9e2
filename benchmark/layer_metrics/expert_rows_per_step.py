"""Rows the held routed experts computed a step, summed over the expert layers
(program counter): the mean over the traced steps of the step's own
``model/expert_rows`` times the held experts of every expert layer, as the
builder keeps it in ``constants["expert_rows_per_step"]``.  At uniform routing
it is tokens x experts per token x held / routed a layer; a collapsed router
moves it.  A program that keeps no such counter reads nothing."""

UNIT = "rows"


def read(ctx):
    return ctx.constants.get("expert_rows_per_step")
