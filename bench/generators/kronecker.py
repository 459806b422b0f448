"""Graph500's Kronecker (R-MAT) graph, drawn on the device.

The edge tuples are drawn with threefry, so every backend draws the same
bits, then symmetrized and de-duplicated by one device sort.  The host
only compresses the arrays.  Isolated vertices stay isolated: Graph500
draws search keys from vertices of degree >= 1 instead of adding linking
edges.

Configuration keys: ``scale``, ``edge_factor``, ``a``, ``b``, ``c`` (the
initiator's probabilities) and ``seed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _threshold(p: float) -> int:
    """uint32 threshold ``t`` with ``P(bits >= t) = 1 - p``."""
    return min(int(round(p * 2**32)), 2**32 - 1)


@functools.partial(
    jax.jit, static_argnames=("scale", "edge_factor", "thresholds")
)
def _kronecker_device(key, *, scale: int, edge_factor: int, thresholds):
    t_ab, t_anorm, t_cnorm = (jnp.uint32(t) for t in thresholds)
    n = 1 << scale
    m = n * edge_factor
    k_bits, k_perm = jax.random.split(key)

    def level(i, carry):
        s, d = carry
        k_row, k_col = jax.random.split(jax.random.fold_in(k_bits, i))
        row_bit = jax.random.bits(k_row, (m,), jnp.uint32) >= t_ab
        col_t = jnp.where(row_bit, t_cnorm, t_anorm)
        col_bit = jax.random.bits(k_col, (m,), jnp.uint32) >= col_t
        return (s << 1) | row_bit.astype(jnp.int32), (d << 1) | col_bit.astype(
            jnp.int32
        )

    zeros = jnp.zeros(m, jnp.int32)
    s, d = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    # a permutation by sorting random keys, ties broken by index, so that
    # no backend's choice of sort order changes the graph
    _, perm = jax.lax.sort(
        (jax.random.bits(k_perm, (n,), jnp.uint32), jnp.arange(n, dtype=jnp.int32)),
        num_keys=2,
    )
    s, d = perm[s], perm[d]
    src = jnp.concatenate([s, d])
    dst = jnp.concatenate([d, s])
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros(1, bool), (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])]
    )
    return src, dst, (src != dst) & ~dup


def kronecker_arcs(
    scale: int, edge_factor: int, a: float, b: float, c: float, seed: int
) -> tuple[np.ndarray, np.ndarray]:
    """Graph500 Kronecker graph: ``(src, dst)`` int32 arcs on ``2**scale``
    vertices.  Per level, the row bit is set with probability ``1 - a - b``
    and the column bit with ``1 - a/(a+b)`` or ``1 - c/(c+d)`` given it;
    vertex ids are then permuted."""
    ab = a + b
    thresholds = (_threshold(ab), _threshold(a / ab), _threshold(c / (1.0 - ab)))
    key = jax.random.key(int(seed), impl="threefry2x32")
    src, dst, keep = jax.device_get(
        _kronecker_device(
            key, scale=int(scale), edge_factor=int(edge_factor),
            thresholds=thresholds,
        )
    )
    return src[keep], dst[keep]


def arcs(graph: dict) -> tuple[int, np.ndarray, np.ndarray]:
    src, dst = kronecker_arcs(
        graph["scale"], graph["edge_factor"], graph["a"], graph["b"], graph["c"],
        graph["seed"],
    )
    return 1 << int(graph["scale"]), src, dst
