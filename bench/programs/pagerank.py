"""PageRank: whole runs of ``iterations`` supersteps with ``damping``.

The program's semantics, which the reference follows: every superstep sets
``rank[v] = (1 - d)/n + d * sum_{u->v} rank[u] / deg[u]``, and the rank
of vertices with no edges is not redistributed (LDBC Graphalytics'
PageRank redistributes it; the rate, EVPS, is Graphalytics').  Compared
with a float64 power iteration (``rank_rel_err``, the largest relative
error of any vertex) and with the superstep count (``superstep_mismatch``).
The control is the power iteration in bfloat16, the precision below the
program's float32.
"""

import numpy as np

from bench import harness as h
from bench import reference as ref

READS_WEIGHT = True  # the 1/out-degree plane


def make(traffic):
    from repro.graph.program import PageRankProgram

    return PageRankProgram(traffic["damping"], traffic["iterations"])


def compare(cell, setup, batches):
    _, dist = h.stacked(batches)
    want = ref.pagerank(setup.n, setup.src, setup.dst, cell.traffic["damping"],
                        cell.traffic["iterations"])
    errs = np.array([ref.max_rel_err(row, want, 0.0) for row in dist])
    steps = np.abs(np.concatenate([b.n_supersteps for b in batches])
                   - cell.traffic["iterations"])
    values = {"rank_rel_err": float(errs.max()),
              "superstep_mismatch": int(steps.sum())}
    failed = (errs > cell.limits["rank_rel_err"]) | (steps > 0)
    return values, int(failed.sum())


def control(cell, setup, batches):
    import jax.numpy as jnp

    rank = ref.pagerank_lowp(setup.n, setup.src, setup.dst, cell.traffic["damping"],
                             cell.traffic["iterations"], jnp.bfloat16)
    return [np.tile(rank, (b.keys.size, 1)) for b in batches]
