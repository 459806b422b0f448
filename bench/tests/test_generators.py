"""The benchmark's graph generators: deterministic per seed, the same
graph on every backend, and the shapes their configurations state; and
the configuration's generator found by its name."""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from bench import graphs, load_module

kronecker = load_module("generators", "kronecker")

#: sha256 prefixes of (src, dst) of the Kronecker graph with seed 42, edge
#: factor 16, as a TPU v5e generated it; the CPU must draw the same graph
CHIP_HASHES = {10: "0e26c6eff14e0d90", 12: "2ac8a4ce4110d2f1"}


def _digest(src, dst) -> str:
    return hashlib.sha256(src.tobytes() + dst.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("scale", sorted(CHIP_HASHES))
def test_kronecker_matches_the_graph_the_chip_drew(scale):
    src, dst = kronecker.kronecker_arcs(scale, 16, 0.57, 0.19, 0.19, 42)
    assert _digest(src, dst) == CHIP_HASHES[scale]


def test_kronecker_is_deterministic_per_seed():
    a = kronecker.kronecker_arcs(9, 16, 0.57, 0.19, 0.19, 7)
    b = kronecker.kronecker_arcs(9, 16, 0.57, 0.19, 0.19, 7)
    c = kronecker.kronecker_arcs(9, 16, 0.57, 0.19, 0.19, 8)
    assert _digest(*a) == _digest(*b) != _digest(*c)


def test_kronecker_arcs_are_symmetric_sorted_and_simple():
    n = 1 << 9
    src, dst = kronecker.kronecker_arcs(9, 16, 0.57, 0.19, 0.19, 3)
    key = src.astype(np.int64) * n + dst
    assert np.all(np.diff(key) > 0)  # sorted, no duplicates
    assert not np.any(src == dst)
    assert np.array_equal(np.sort(dst.astype(np.int64) * n + src), key)
    # Graph500's skew: a heavy hub and isolated vertices, no linking edges
    deg = np.bincount(src, minlength=n)
    assert deg.max() > 20 * deg.mean() / 2 and (deg == 0).any()


def test_hash_weights_are_symmetric_and_in_range():
    src, dst = kronecker.kronecker_arcs(8, 16, 0.57, 0.19, 0.19, 1)
    w = graphs.hash_weights(src, dst, 256, 1.0, 4.0)
    assert w.dtype == np.float32 and w.min() >= 1.0 and w.max() < 4.0
    back = {(int(s), int(d)): x for s, d, x in zip(src, dst, w)}
    assert all(back[(d, s)] == x for (s, d), x in back.items())


def test_make_arcs_finds_the_generator_by_name():
    config = {"graph": {"generator": "kronecker", "scale": 8, "edge_factor": 16,
                        "a": 0.57, "b": 0.19, "c": 0.19, "seed": 3,
                        "weights": [1.0, 4.0]}}
    n, src, dst, w = graphs.make_arcs(config)
    want = kronecker.kronecker_arcs(8, 16, 0.57, 0.19, 0.19, 3)
    assert n == 256 and _digest(src, dst) == _digest(*want)
    assert np.array_equal(w, graphs.hash_weights(src, dst, n, 1.0, 4.0))
    config["graph"]["generator"] = "no_such_generator"
    with pytest.raises(SystemExit, match="no generators module"):
        graphs.make_arcs(config)
