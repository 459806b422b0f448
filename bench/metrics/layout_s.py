"""Host seconds of the set-up's partitioned_edge_layout call."""


def read(ctx):
    return ctx.setup_seconds["layout_s"]
