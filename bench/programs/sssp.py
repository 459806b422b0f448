"""SSSP on the configuration's weights: float32 distances from each key.

Compared with scipy's float64 Dijkstra (``dist_rel_err``, the largest
``|d - d64| / max(d64, 1)``), with the exact float32 fixpoint check
(``fixpoint_violations``, vertices that are not their own one-step
relaxation) and with each key's edge count that ``teps`` sums.  The
control is Bellman-Ford in bfloat16, the precision below the program's
float32.
"""

import numpy as np

from bench import harness as h
from bench import reference as ref

READS_WEIGHT = True


def make(traffic):
    from repro.graph.program import SsspProgram

    return SsspProgram()


def compare(cell, setup, batches):
    keys, dist = h.stacked(batches)
    want = ref.dijkstra(ref.adjacency(setup.n, setup.src, setup.dst, setup.weights),
                        keys)
    errs = np.array([ref.max_rel_err(d, w, 1.0) for d, w in zip(dist, want)])
    fix = ref.sssp_fixpoint_violations(setup.src, setup.dst, setup.weights, keys, dist)
    edge_bad = h.edge_count_bad(setup, batches)
    values = {"dist_rel_err": float(errs.max()),
              "fixpoint_violations": int(fix.sum()),
              "edge_count_mismatch": int(edge_bad.sum())}
    failed = (errs > cell.limits["dist_rel_err"]) | (fix > 0) | edge_bad
    return values, int(failed.sum())


def control(cell, setup, batches):
    import jax.numpy as jnp

    return [ref.bellman_ford_lowp(setup.n, setup.src, setup.dst, setup.weights,
                                  b.keys, jnp.bfloat16) for b in batches]
