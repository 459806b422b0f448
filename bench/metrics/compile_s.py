"""Seconds of JAX's trace, lowering and compile events during set-up."""


def read(ctx):
    return ctx.setup_seconds["compile_s"]
