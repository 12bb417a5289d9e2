"""Device time of the cross-attention kernels per step (device trace): the
Pallas calls whose innermost scope is ``tcdp.attn_cross``, whatever their
names (today ``flash_attn_fwd``, run again under rematerialisation, and the
one ``flash_attn_bwd``, on an earlier layer's keys and values).  The
self-attention layers' kernels are under ``tcdp.attn`` and
``tcdp.attn_window`` and are ``flash_attn_device_ms``'s and
``window_attn_device_ms``'s.  A program without the scope or the kernels reads
nothing."""

UNIT = "ms"


def is_cross_kernel(name: str, scope: str, kind: str) -> bool:
    return scope == "attn_cross" and kind.startswith("pallas")


def kernel_seconds(ctx):
    """Seconds a device spent in the cross-attention kernels over the traced
    window."""
    if ctx.extract is None or not ctx.traced_steps:
        return 0.0
    return ctx.reduce.device_seconds(ctx.extract, is_cross_kernel)


def read(ctx):
    seconds = kernel_seconds(ctx)
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
