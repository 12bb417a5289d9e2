"""Device time of the attention kernels per step (device trace): the Pallas
calls under the ``tcdp.attn`` scope, whatever their names (today
``flash_attn_fwd`` and the one ``flash_attn_bwd``; a forward run again under
rematerialisation counts, it took the time).  A program without the scope or
the kernels reads nothing."""

UNIT = "ms"


def is_attention_kernel(name: str, scope: str, kind: str) -> bool:
    return scope == "attn" and kind.startswith("pallas")


def kernel_seconds(ctx):
    """Seconds a device spent in the attention kernels over the traced window."""
    if ctx.extract is None or not ctx.traced_steps:
        return 0.0
    return ctx.reduce.device_seconds(ctx.extract, is_attention_kernel)


def read(ctx):
    seconds = kernel_seconds(ctx)
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
