"""``fused_select_pack``'s share of its memory roofline: the least bytes it
must move (benchmark's ``flops.select_pack_min_bytes``) over the chip's HBM
bandwidth, over its device time.  Memory bounds it: it does no matrix work."""

UNIT = "%"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps or not ctx.compressed:
        return None
    ratio = ctx.traffic["compression"]["ratio"]
    nbytes, launches = ctx.flops.select_pack_min_bytes(
        ctx.flops.leaf_sizes(ctx.model, ctx.cfg),
        lambda n: ctx.sync.keep_count(n, ratio),
        ctx.constants["select_pack_min_elems"])   # the program's own threshold
    seconds = ctx.reduce.device_seconds(ctx.extract, ctx.reduce.is_select_pack) / ctx.traced_steps
    if seconds <= 0:
        return None
    return 100.0 * (nbytes / ctx.peaks["hbm_bytes_per_s"]) / seconds
