"""Device time of the sliding-window attention kernels per step (device
trace): the Pallas calls whose innermost scope is ``tcdp.attn_window``,
whatever their names (today the banded ``flash_attn_fwd``, run again under
rematerialisation, and the one ``flash_attn_bwd``).  The full layers' kernels
are under ``tcdp.attn`` and are ``flash_attn_device_ms``'s.  A program without
the scope or the kernels reads nothing."""

UNIT = "ms"


def is_window_kernel(name: str, scope: str, kind: str) -> bool:
    return scope == "attn_window" and kind.startswith("pallas")


def kernel_seconds(ctx):
    """Seconds a device spent in the window kernels over the traced window."""
    if ctx.extract is None or not ctx.traced_steps:
        return 0.0
    return ctx.reduce.device_seconds(ctx.extract, is_window_kernel)


def read(ctx):
    seconds = kernel_seconds(ctx)
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
