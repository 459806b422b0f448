"""The relax kernel's share of its memory roofline: the least bytes of the
window's relax passes (bench/roofline.py) at the device's HBM peak, over
the kernel's device time."""


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_s <= 0:
        return None
    least_s = ctx.relax_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace.kernel_s
