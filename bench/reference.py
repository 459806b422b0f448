"""Plain references for the comparison that decides ``correct``.

Everything here is scipy and numpy over the benchmark's own arcs; nothing
is imported from the program, and nothing the program made is read.

* ``bfs_hops``: hop counts from each key, from scipy's breadth-first order
  and its predecessor tree (pointer jumping gives every vertex its depth).
* ``dijkstra``: float64 shortest-path distances.
* ``sssp_fixpoint_violations``: the exact check of a float32 SSSP: the
  vertices whose distance is not ``min(init, min_{u->v} dist[u] + w)`` in
  float32.  With weights >= 1 that fixpoint is unique, and it is what
  float32 Bellman-Ford converges to.
* ``pagerank``: float64 power iteration,
  ``(1 - d)/n + d * sum_{u->v} rank[u] / deg[u]``, with no redistribution
  of the mass of vertices that have no edges: the program's semantics, not
  LDBC Graphalytics', which redistributes it.
* ``component_edges``: for each vertex, the undirected edges inside its
  connected component: what Graph500 counts for a search key.

The controls beside them are the same references with one guarantee broken
or computed in a lower precision; the program kinds in ``bench/programs/``
use both.
"""

from __future__ import annotations

import numpy as np


def adjacency(n: int, src: np.ndarray, dst: np.ndarray, weights=None):
    from scipy import sparse

    w = np.ones(src.size) if weights is None else np.asarray(weights, np.float64)
    return sparse.csr_matrix((w, (src, dst)), shape=(n, n))


def bfs_hops(adj, keys) -> np.ndarray:
    """``[K, n]`` float64 hop counts from each key, inf where unreachable."""
    from scipy.sparse import csgraph

    n = adj.shape[0]
    out = np.full((len(keys), n), np.inf)
    for row, key in zip(out, keys):
        order, pred = csgraph.breadth_first_order(
            adj, int(key), directed=True, return_predecessors=True
        )
        parent = np.where(pred < 0, np.arange(n), pred)
        depth = (pred >= 0).astype(np.int64)
        # pointer jumping: after j rounds parent[v] is v's 2**j-th ancestor
        # (or the key), and depth[v] the hops up to it
        while True:
            grand = parent[parent]
            if np.array_equal(grand, parent):
                break
            depth = depth + depth[parent]
            parent = grand
        row[order] = depth[order]
    return out


def dijkstra(adj, keys) -> np.ndarray:
    """``[K, n]`` float64 shortest-path distances from each key."""
    from scipy.sparse import csgraph

    return csgraph.dijkstra(adj, directed=True, indices=np.asarray(keys))


def sssp_fixpoint_violations(
    src: np.ndarray, dst: np.ndarray, w: np.ndarray, keys, dist: np.ndarray
) -> np.ndarray:
    """``[K]``: the vertices of each row whose float32 ``dist`` is not the
    float32 one-step relaxation of itself."""
    order = np.argsort(dst, kind="stable")
    d_src, d_dst = src[order], dst[order]
    w32 = np.asarray(w, np.float32)[order]
    heads = np.flatnonzero(np.r_[True, d_dst[1:] != d_dst[:-1]])
    bad = []
    for row, key in zip(np.asarray(dist, np.float32), keys):
        best = np.full(row.size, np.inf, np.float32)
        best[d_dst[heads]] = np.minimum.reduceat(row[d_src] + w32, heads)
        best[key] = min(best[key], np.float32(0))
        bad.append(np.count_nonzero(row != best))
    return np.array(bad, np.int64)


def pagerank(n, src, dst, damping: float, iterations: int, dtype=np.float64):
    """``[n]`` power iteration in ``dtype`` (float64 for the reference)."""
    from scipy import sparse

    deg = np.bincount(src, minlength=n)
    inv = (1.0 / np.maximum(deg, 1)).astype(dtype)
    m = sparse.csr_matrix((inv[src], (dst, src)), shape=(n, n), dtype=dtype)
    rank = np.full(n, 1.0 / n, dtype)
    base, d = dtype(1.0 - damping) / dtype(n), dtype(damping)
    for _ in range(iterations):
        rank = (base + d * (m @ rank)).astype(dtype)
    return rank


def component_edges(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """``[n]`` int64: undirected edges in each vertex's component."""
    from scipy.sparse import csgraph

    _, label = csgraph.connected_components(adjacency(n, src, dst), directed=False)
    arcs = np.bincount(label[src], minlength=label.max() + 1)
    return (arcs // 2)[label]


def max_rel_err(got: np.ndarray, ref: np.ndarray, floor: float) -> float:
    """Largest ``|got - ref| / max(|ref|, floor)`` over the entries finite
    in both; inf where one is finite and the other is not."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    fin = np.isfinite(ref)
    if not np.array_equal(fin, np.isfinite(got)):
        return float("inf")
    if not fin.any():
        return 0.0
    err = np.abs(got[fin] - ref[fin]) / np.maximum(np.abs(ref[fin]), floor)
    return float(err.max())


# -- controls ---------------------------------------------------------------


def bfs_hops_truncated(hops: np.ndarray) -> np.ndarray:
    """The BFS control: each key's deepest level left unreached, as a
    traversal that stops one superstep short would leave it."""
    out = hops.copy()
    for row in out:
        fin = np.isfinite(row)
        if fin.any():
            row[row == row[fin].max()] = np.inf
    return out


def bellman_ford_lowp(n, src, dst, w, keys, dtype):
    """SSSP control: Bellman-Ford with every sum and minimum in ``dtype``
    (bfloat16 for the float32 program), run with jax on the default
    device; ``[K, n]`` float64."""
    import jax
    import jax.numpy as jnp

    order = np.argsort(dst, kind="stable")
    s = jnp.asarray(src[order])
    d = jnp.asarray(dst[order])
    wd = jnp.asarray(np.asarray(w, np.float32)[order]).astype(dtype)

    @jax.jit
    def solve(dist):
        def body(c):
            cur, _ = c
            relaxed = jax.ops.segment_min(
                cur[s] + wd, d, num_segments=n, indices_are_sorted=True
            )
            new = jnp.minimum(cur, relaxed)
            return new, jnp.any(new < cur)

        return jax.lax.while_loop(lambda c: c[1], body, (dist, jnp.bool_(True)))[0]

    rows = []
    for key in keys:
        init = jnp.full(n, jnp.inf, dtype).at[int(key)].set(0)
        rows.append(np.asarray(solve(init).astype(jnp.float32), np.float64))
    return np.stack(rows)


def pagerank_lowp(n, src, dst, damping: float, iterations: int, dtype):
    """PageRank control: the power iteration with every product and sum in
    ``dtype`` (bfloat16 for the float32 program), run with jax on the
    default device; ``[n]`` float64."""
    import jax
    import jax.numpy as jnp

    order = np.argsort(dst, kind="stable")
    s = jnp.asarray(src[order])
    d = jnp.asarray(dst[order])
    deg = np.bincount(src, minlength=n)
    inv = jnp.asarray(1.0 / np.maximum(deg, 1), jnp.float32).astype(dtype)
    base = jnp.asarray((1.0 - damping) / n, dtype)
    damp = jnp.asarray(damping, dtype)

    @jax.jit
    def solve(rank):
        def step(_, r):
            acc = jax.ops.segment_sum(r[s] * inv[s], d, num_segments=n,
                                      indices_are_sorted=True)
            return base + damp * acc

        return jax.lax.fori_loop(0, iterations, step, rank)

    rank = solve(jnp.full(n, 1.0 / n, dtype))
    return np.asarray(rank.astype(jnp.float32), np.float64)
