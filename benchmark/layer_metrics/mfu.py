"""Model FLOP utilisation: 3 x forward FLOPs of the configuration's shapes
(benchmark's ``flops.train_flops_per_sample``) x samples/s of the traced
window, over chips x the chip's published bf16 peak."""

UNIT = "%"


def read(ctx):
    if not ctx.traced_rate:
        return None
    return 100.0 * ctx.flops.train_flops_per_sample(ctx.model, ctx.cfg) * ctx.traced_rate / (
        ctx.chips * ctx.peaks["bf16_flops"])
