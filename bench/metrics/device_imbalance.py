"""How far the busiest device's busy time in the traced window lies above
the devices' mean: 0 where the work is spread evenly."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (max(ctx.trace.device_busy_s) / ctx.trace.busy_s - 1.0)
