"""The benchmark's graphs: the yardstick's data, made from a configuration's
seed and never from the program's generators.

A configuration's ``graph.generator`` names a module of its own,
``bench/generators/<generator>.py``, whose ``arcs(graph)`` returns ``(n,
src, dst)``: int32 arcs sorted by ``(src, dst)``, both directions of each
undirected edge, no self-loops, no duplicates.  ``graph.weights``, where
given as ``[low, high]``, adds ``hash_weights`` over those arcs.
"""

from __future__ import annotations

import numpy as np

from bench import ROOT, load_module


def hash_weights(
    src: np.ndarray, dst: np.ndarray, n: int, low: float, high: float
) -> np.ndarray:
    """float32 weights in ``[low, high)``, equal for ``(u, v)`` and
    ``(v, u)``: a multiplicative hash of ``min * n + max``."""
    u = np.minimum(src, dst).astype(np.uint64)
    v = np.maximum(src, dst).astype(np.uint64)
    with np.errstate(over="ignore"):  # wrapping arithmetic is the hash
        h = ((u * np.uint64(n) + v) * np.uint64(2654435761)) & np.uint64(2**31 - 1)
    return (low + (high - low) * (h.astype(np.float64) / 2**31)).astype(np.float32)


def make_arcs(config: dict, root: str = ROOT):
    """``(n, src, dst, weights or None)`` for a configuration's ``graph``."""
    g = config["graph"]
    n, src, dst = load_module("generators", g["generator"], root).arcs(g)
    weights = hash_weights(src, dst, n, *g["weights"]) if "weights" in g else None
    return n, src, dst, weights
