"""The convolution and scan's share of their roofline: what the traced steps
need of them (the model file's ``ssd_flops_per_sample`` over the chip's bf16
peak, or its ``ssd_min_bytes_per_sample`` over the HBM bandwidth, whichever
takes longer; times 3 for forward and backward, nothing recomputed counted)
over the device time under ``tcdp.ssd``.  The numerator knows nothing of what
implements the scan.  A program without the scope, or a model file without
the two functions, reads nothing."""

UNIT = "%"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps or not hasattr(
            ctx.model, "ssd_flops_per_sample"):
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("ssd",))
    if seconds <= 0:
        return None
    least = max(ctx.model.ssd_flops_per_sample(ctx.cfg) / ctx.peaks["bf16_flops"],
                ctx.model.ssd_min_bytes_per_sample(ctx.cfg)
                / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * 3.0 * least * ctx.cfg["per_chip_batch"] * ctx.traced_steps / seconds
