"""Share of the traced window in which the relax kernel ran on the device."""


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_s <= 0:
        return None
    return 100.0 * ctx.trace.kernel_s / ctx.trace.window_s
