"""BFS, Graph500's kernel 2: hop counts from each key.

Compared exactly with scipy's breadth-first order (``hop_mismatch``,
vertices whose hop count differs), with each key's edge count that
``teps`` sums (``edge_count_mismatch``, keys).  BFS states no precision:
hop counts are small integers, exact in any float type that holds them.
So its control breaks the guarantee that every reachable vertex gets its
hop count: each key's deepest level is left unreached.
"""

from bench import harness as h
from bench import reference as ref

READS_WEIGHT = False  # a relax pass reads no weight per arc


def make(traffic):
    from repro.graph.program import BfsProgram

    return BfsProgram()


def _hops(setup, keys):
    return ref.bfs_hops(ref.adjacency(setup.n, setup.src, setup.dst), keys)


def compare(cell, setup, batches):
    keys, dist = h.stacked(batches)
    row_bad = (dist != _hops(setup, keys)).sum(axis=1)
    edge_bad = h.edge_count_bad(setup, batches)
    values = {"hop_mismatch": int(row_bad.sum()),
              "edge_count_mismatch": int(edge_bad.sum())}
    return values, int(((row_bad > 0) | edge_bad).sum())


def control(cell, setup, batches):
    return [ref.bfs_hops_truncated(_hops(setup, b.keys)) for b in batches]
