"""Device time of the Pallas kernels (``tpu_custom_call`` events) per step."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps or not ctx.compressed:
        return None
    return 1e3 * ctx.reduce.device_seconds(ctx.extract, ctx.reduce.is_pallas) / ctx.traced_steps
