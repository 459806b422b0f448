"""Host seconds of the set-up's bfs_grow_partition call."""


def read(ctx):
    return ctx.setup_seconds["partition_s"]
