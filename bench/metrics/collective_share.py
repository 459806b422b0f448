"""Share of the traced window in which a collective (all-to-all,
all-reduce, ...) ran on a device, averaged over the devices."""


def read(ctx):
    if ctx.trace is None or ctx.trace.collective_s <= 0:
        return None
    return 100.0 * ctx.trace.collective_s / ctx.trace.window_s
