"""The step's own ``comm/sync_chains`` counter: the chains its wire sync was
traced as, one per distinct (flat size, dtype, transport) among the reduction
groups.  A program without the counter gives nothing."""

UNIT = "chains"


def read(ctx):
    if not ctx.compressed:
        return None
    return ctx.counters.get("comm/sync_chains")
