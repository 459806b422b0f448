"""Host seconds of the set-up's layout build: partitioned_edge_layout on
one chip, mesh_edge_layout over a mesh."""


def read(ctx):
    return ctx.setup_seconds["layout_s"]
