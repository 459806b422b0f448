"""Share of the traced window in which the device ran anything but the
relax kernel: the engine's XLA ops (gathers, masks, counters, loop)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (ctx.trace.busy_s - ctx.trace.kernel_s) / ctx.trace.window_s
