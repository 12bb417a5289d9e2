"""The grouped product's share of its compute roofline: the model file's
``experts_flops_per_row`` (forward operations of one routed row through one
expert) x the rows the held experts computed a step (the program's counter,
``expert_rows_per_step``) x 3 for forward and backward, over the chip's bf16
peak, over the device time under ``tcdp.experts``.  The numerator knows
nothing of what implements the product; padded rows of a tile count against
it.  A program without the scope or the counter reads nothing."""

from layer_metrics.experts_device_ms import seconds_under_experts

UNIT = "%"


def read(ctx):
    seconds = seconds_under_experts(ctx)
    rows = ctx.constants.get("expert_rows_per_step")
    if seconds <= 0 or not rows or not hasattr(ctx.model, "experts_flops_per_row"):
        return None
    flops = 3.0 * ctx.model.experts_flops_per_row(ctx.cfg) * rows * ctx.traced_steps
    return 100.0 * (flops / ctx.peaks["bf16_flops"]) / seconds
