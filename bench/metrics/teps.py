"""Graph500 TEPS: for every key of every batch in the window, the
undirected edges the engine reached from it (its component, where the
answer is right), summed, over the window's host seconds."""


def read(ctx):
    return sum(int(b.edges.sum()) for b in ctx.batches) / ctx.window_s
