"""Host seconds from process start until the chip is attached: Python,
the jax import and the runtime's look for the chip, before any of the
benchmark's own set-up."""


def read(ctx):
    return ctx.setup_seconds["start_s"]
