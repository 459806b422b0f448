"""Relax passes a batch makes: local closure passes plus remote passes
(``bench/roofline.py`` ``passes``), the mean over the window's batches.
The counters are the program's own; read where the trace holds device
work, as the window's other per-layer metrics are."""

from bench.roofline import passes


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0 or not ctx.batches:
        return None
    per_batch = [sum(passes(b.n_supersteps, b.inner_iters, stationary=False))
                 for b in ctx.batches]
    return sum(per_batch) / len(per_batch)
