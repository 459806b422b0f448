"""LDBC Graphalytics EVPS: (vertices + undirected edges) of every whole
run in the window, summed, over the window's host seconds."""


def read(ctx):
    return len(ctx.batches) * (ctx.n + ctx.n_edges) / ctx.window_s
