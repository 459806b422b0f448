"""The relax kernel's share of its memory roofline: the least bytes of the
window's relax passes (bench/roofline.py) at the HBM peak of the devices
that share them, over the kernel's device time averaged over the devices."""


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_s <= 0:
        return None
    peak = ctx.peaks["hbm_bytes_per_s"] * ctx.trace.n_devices
    least_s = ctx.relax_bytes / peak
    return 100.0 * least_s / ctx.trace.kernel_s
