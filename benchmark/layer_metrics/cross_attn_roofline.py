"""The cross-attention kernels' share of their compute roofline: the
operations the cross layers' attention needs for the traced steps (the model
file's ``cross_attention_flops_per_sample``: two products forward and four
backward a softmax map and layer over the causal half of T x T, keys of the
head's width and values of twice that, nothing padded or recomputed counted)
over the chip's bf16 peak, over the kernels' device time.  The numerator knows
nothing of the kernels: columns they pad a key to, and a forward run again,
count against it."""

from layer_metrics.cross_attn_device_ms import kernel_seconds

UNIT = "%"


def read(ctx):
    seconds = kernel_seconds(ctx)
    if seconds <= 0 or not hasattr(ctx.model, "cross_attention_flops_per_sample"):
        return None
    flops = (ctx.model.cross_attention_flops_per_sample(ctx.cfg)
             * ctx.cfg["per_chip_batch"] * ctx.traced_steps)
    return 100.0 * (flops / ctx.peaks["bf16_flops"]) / seconds
