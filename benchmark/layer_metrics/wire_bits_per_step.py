"""The step's own ``comm/sent_bits`` counter: one worker's payload a step."""

UNIT = "bits"


def read(ctx):
    if not ctx.compressed:
        return None
    return ctx.counters.get("comm/sent_bits")
