"""The references that decide ``correct``, and the per-key edge count that
``teps`` is built from, against scipy and the engine on small graphs."""

from __future__ import annotations

import numpy as np
from scipy.sparse import csgraph

from bench import graphs, harness, load_module, reference, roofline

kronecker = load_module("generators", "kronecker")


def _kronecker(scale=9, seed=5):
    n = 1 << scale
    src, dst = kronecker.kronecker_arcs(scale, 16, 0.57, 0.19, 0.19, seed)
    return n, src, dst


def test_bfs_hops_equal_scipy_shortest_paths():
    n, src, dst = _kronecker()
    adj = reference.adjacency(n, src, dst)
    keys = np.flatnonzero(np.bincount(src, minlength=n))[:6]
    want = csgraph.shortest_path(adj, method="D", unweighted=True, indices=keys)
    assert np.array_equal(reference.bfs_hops(adj, keys), want)


def test_per_key_edge_count_from_the_engine_equals_the_component_count():
    """``teps`` counts, for each key, the edges among the vertices the
    engine reached: the key's component, as scipy labels it."""
    from repro.graph.config import EngineConfig
    from repro.graph.partition import bfs_grow_partition
    from repro.graph.program import BfsProgram
    from repro.graph.session import open_session
    from repro.graph.structs import Graph

    n, src, dst = _kronecker()
    degree = np.bincount(src, minlength=n)
    # keys in the giant component, in small ones, and of degree 0
    _, label = csgraph.connected_components(
        reference.adjacency(n, src, dst), directed=False
    )
    sizes = np.bincount(label)
    small = np.flatnonzero((sizes[label] > 1) & (sizes[label] < sizes.max()))
    keys = np.r_[np.flatnonzero(sizes[label] == sizes.max())[:4], small[:2],
                 np.flatnonzero(degree == 0)[:2]][:8]
    session = open_session(bfs_grow_partition(Graph(n, src, dst), 4, seed=1),
                           EngineConfig(backend="xla"))
    res = session.run(BfsProgram(), keys)
    got = harness.reached_edges(degree, res.dist)
    want = reference.component_edges(n, src, dst)[keys]
    assert np.array_equal(got, want)


def test_sssp_fixpoint_check_accepts_the_fixpoint_and_nothing_else():
    n, src, dst = _kronecker(scale=8, seed=1)
    w = graphs.hash_weights(src, dst, n, 1.0, 4.0)
    keys = list(np.argsort(np.bincount(src, minlength=n))[-2:])
    dist = reference.bellman_ford_lowp(n, src, dst, w, keys, np.float32)
    assert reference.sssp_fixpoint_violations(src, dst, w, keys, dist).sum() == 0
    v = int(np.flatnonzero(np.isfinite(dist[1]) & (dist[1] > 0))[0])
    dist[1, v] += 1.0
    assert list(reference.sssp_fixpoint_violations(src, dst, w, keys, dist)) != [0, 0]


def test_pass_bytes_take_the_cheaper_read_of_each_arc():
    # one row: dst id + its candidate (8 bytes an arc); 8 rows: dst, src
    # and weight ids (12); BFS reads no weight (8); state rows read and written
    assert roofline.pass_bytes(100, 10, 1, True) == 100 * 8 + 2 * 4 * 10
    assert roofline.pass_bytes(100, 10, 8, True) == 100 * 12 + 2 * 4 * 8 * 10
    assert roofline.pass_bytes(100, 10, 8, False) == 100 * 8 + 2 * 4 * 8 * 10


def test_passes_count_the_batch_loop_not_each_row():
    nst = np.array([2, 3])
    inner = np.array([[4, 1, 0, 0], [2, 5, 1, 0]])
    assert roofline.passes(nst, inner, False) == (4 + 5 + 1, 3)
    assert roofline.passes(np.array([20]), None, True) == (20, 20)
