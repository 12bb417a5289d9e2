"""The attention kernels' share of their compute roofline: the operations
attention needs for the traced steps (the model file's
``attention_flops_per_sample``: two products forward and four backward a head
and layer-pass, the causal half of T x T x head size, nothing recomputed
counted) over the chip's bf16 peak, over the kernels' device time.  The MXU
bounds it: at 4,096 tokens a head's products are 2,048 FLOPs to the byte of
q, k and v.  The numerator knows nothing of the kernels, so the share reads
the same whatever implements attention."""

from layer_metrics.flash_attn_device_ms import kernel_seconds

UNIT = "%"


def read(ctx):
    seconds = kernel_seconds(ctx)
    if seconds <= 0 or not hasattr(ctx.model, "attention_flops_per_sample"):
        return None
    flops = (ctx.model.attention_flops_per_sample(ctx.cfg)
             * ctx.cfg["per_chip_batch"] * ctx.traced_steps)
    return 100.0 * (flops / ctx.peaks["bf16_flops"]) / seconds
