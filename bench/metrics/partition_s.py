"""Host seconds of the set-up's call of the configuration's partitioner."""


def read(ctx):
    return ctx.setup_seconds["partition_s"]
