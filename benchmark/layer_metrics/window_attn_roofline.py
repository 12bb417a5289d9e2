"""The sliding-window attention kernels' share of their compute roofline: the
operations the window layers' attention needs for the traced steps (the model
file's ``window_attention_flops_per_sample``: two products forward and four
backward a head and layer over the band's pairs, T x window less the first
window's triangle, nothing recomputed counted) over the chip's bf16 peak, over
the kernels' device time.  The numerator knows nothing of the kernels: pairs
outside the band that a block-wise kernel visits and masks count against it."""

from layer_metrics.window_attn_device_ms import kernel_seconds

UNIT = "%"


def read(ctx):
    seconds = kernel_seconds(ctx)
    if seconds <= 0 or not hasattr(ctx.model, "window_attention_flops_per_sample"):
        return None
    flops = (ctx.model.window_attention_flops_per_sample(ctx.cfg)
             * ctx.cfg["per_chip_batch"] * ctx.traced_steps)
    return 100.0 * (flops / ctx.peaks["bf16_flops"]) / seconds
