"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
against the pure-jnp oracles + hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph.structs import dst_sorted_layout
from repro.kernels.bfs_relax import reference_bfs_relax
from repro.kernels.flash_attention import flash_attention, reference_attention
from repro.kernels.segment_sum import reference_segment_sum, sorted_segment_sum

from _relax_entry import engine_relax

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, s, h, hk, d, window, dtype)
    (2, 256, 4, 2, 64, None, jnp.float32),
    (1, 128, 2, 2, 128, None, jnp.float32),
    (2, 256, 4, 4, 64, 64, jnp.float32),
    (1, 160, 2, 1, 48, None, jnp.float32),  # ragged S, MQA, odd head dim
    (1, 512, 8, 2, 64, 128, jnp.float32),
    (2, 256, 4, 2, 64, None, jnp.bfloat16),
    (1, 384, 6, 3, 96, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_oracle(case):
    b, s, h, hk, d, win, dtype = case
    q = jax.random.normal(KEY, (b, s, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hk, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hk, d), dtype)
    out = flash_attention(q, k, v, window=win, interpret=True)
    ref = reference_attention(q, k, v, window=win)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_block_size_invariance():
    q = jax.random.normal(KEY, (1, 256, 2, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 256, 2, 64))
    outs = [
        np.asarray(flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True))
        for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, atol=1e-5, rtol=1e-5)


def test_flash_attention_noncausal():
    q = jax.random.normal(KEY, (1, 128, 2, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 128, 2, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 128, 2, 64))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# segment sum
# ---------------------------------------------------------------------------

SEG_CASES = [
    # (E, D, N, dtype, skew)
    (1024, 64, 256, jnp.float32, "uniform"),
    (2048, 128, 512, jnp.float32, "powerlaw"),
    (777, 32, 100, jnp.float32, "uniform"),  # ragged everything
    (1024, 16, 64, jnp.bfloat16, "uniform"),
    (4096, 75, 512, jnp.float32, "powerlaw"),  # PNA width
    (512, 10, 1000, jnp.float32, "uniform"),  # recsys embed dim, sparse rows
]


def _ids(e, n, skew, seed=0):
    rng = np.random.default_rng(seed)
    if skew == "powerlaw":
        raw = rng.zipf(1.5, e) % n
    else:
        raw = rng.integers(0, n, e)
    return jnp.asarray(raw, jnp.int32)


@pytest.mark.parametrize("case", SEG_CASES)
def test_segment_sum_vs_oracle(case):
    e, d, n, dtype, skew = case
    ids = _ids(e, n, skew)
    vals = jax.random.normal(KEY, (e, d), dtype)
    out = sorted_segment_sum(ids, vals, n, interpret=True)
    ref = reference_segment_sum(ids, vals, n)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)


@given(
    e=st.integers(8, 600),
    n=st.integers(4, 300),
    d=st.sampled_from([4, 16, 33]),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=20, deadline=None)
def test_segment_sum_property(e, n, d, seed):
    ids = _ids(e, n, "uniform", seed)
    vals = jax.random.normal(jax.random.PRNGKey(seed), (e, d))
    out = sorted_segment_sum(ids, vals, n, interpret=True)
    ref = reference_segment_sum(ids, vals, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# bfs relax
# ---------------------------------------------------------------------------

RELAX_CASES = [
    (512, 2048, 0.1),
    (1000, 5000, 0.5),
    (100, 300, 1.0),
    (4096, 16384, 0.05),
]


@pytest.mark.parametrize("case", RELAX_CASES)
def test_bfs_relax_vs_oracle(case):
    n, e, frontier_frac = case
    rng = np.random.default_rng(n + e)
    src = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, e), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, e), jnp.float32)
    dist = jnp.asarray(
        np.where(rng.random(n) < 0.5, rng.uniform(0, 10, n), np.inf), jnp.float32
    )
    frontier = jnp.asarray(rng.random(n) < frontier_frac)
    layout = dst_sorted_layout(n, *map(np.asarray, (src, dst, w)))
    out = engine_relax(dist, frontier, layout)
    ref = reference_bfs_relax(dist, frontier, src, dst, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6, rtol=1e-6)


def test_bfs_relax_full_traversal_matches_engine():
    """Iterating the kernel to fixpoint must produce exact SSSP distances."""
    from repro.graph.generators import erdos_renyi_graph, weighted
    from repro.graph.traversal import reference_sssp
    from repro.graph.partition import hash_partition

    g = weighted(erdos_renyi_graph(300, 5.0, seed=3), seed=1)
    n = g.n_vertices
    layout = dst_sorted_layout(n, g.src, g.dst, g.edge_weights)
    dist = jnp.full((n,), jnp.inf).at[0].set(0.0)
    frontier = jnp.zeros((n,), bool).at[0].set(True)
    for _ in range(n):
        new = engine_relax(dist, frontier, layout)
        frontier = new < dist
        if not bool(frontier.any()):
            break
        dist = new
    ref = reference_sssp(hash_partition(g, 2), 0)
    np.testing.assert_allclose(np.asarray(dist), ref, rtol=1e-6)


def test_bfs_relax_empty_frontier_is_identity():
    n, e = 128, 512
    rng = np.random.default_rng(0)
    dist = jnp.asarray(rng.uniform(0, 5, n), jnp.float32)
    layout = dst_sorted_layout(
        n, rng.integers(0, n, e).astype(np.int32),
        rng.integers(0, n, e).astype(np.int32), np.ones(e, np.float32),
    )
    out = engine_relax(dist, jnp.zeros((n,), bool), layout)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(dist))


# ---------------------------------------------------------------------------
# program-generic relax (the engine backend)
# ---------------------------------------------------------------------------


def _program_pg(n=200, avg_deg=4.0, seed=7):
    from repro.graph.generators import erdos_renyi_graph, weighted
    from repro.graph.partition import hash_partition

    g = weighted(erdos_renyi_graph(n, avg_deg, seed=seed), seed=seed + 1)
    return hash_partition(g, 3)


def _program_layout(pg, prog):
    """dst-sorted layout carrying the program's edge plane as weights."""
    from repro.graph.program import resolve_edge_plane

    g = pg.graph
    plane = resolve_edge_plane(pg, prog)
    w = g.weights if plane is None else plane
    return dst_sorted_layout(g.n_vertices, g.src, g.dst, w)


@pytest.mark.parametrize("name", ["bfs", "sssp", "wcc", "pagerank"])
def test_relax_fn_matches_xla_per_program(name):
    """One relax pass: kernel (interpret) vs the engine's XLA segment ops,
    exact for min programs (WCC's int32 labels included), allclose for the
    float sum path."""
    from repro.graph.program import BUILTIN_PROGRAMS

    prog = BUILTIN_PROGRAMS[name]()
    pg = _program_pg()
    lay = _program_layout(pg, prog)

    rng = np.random.default_rng(42)
    n = pg.graph.n_vertices
    state0, frontier0 = prog.init(pg, np.array([0, 17]))
    # perturb so the pass is non-trivial for min programs
    state = jnp.asarray(state0)
    if name in ("bfs", "sssp"):
        state = state.at[:, ::3].set(
            jnp.asarray(rng.uniform(0, 4, state[:, ::3].shape), state.dtype)
        )
        frontier0 = rng.random(frontier0.shape) < 0.4
    frontier = jnp.asarray(frontier0)
    out = engine_relax(state, frontier, lay, program=prog)

    src, dst, w = map(jnp.asarray, (lay.src, lay.dst, lay.weights))
    ident = prog.identity
    cand = jnp.where(frontier[:, src], prog.relax(state[:, src], w), ident)
    if prog.reduce == "min":
        red = jax.vmap(
            lambda c: jax.ops.segment_min(
                c, dst, num_segments=n, indices_are_sorted=True
            )
        )(cand)
        ref = prog.combine(state, red)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    else:
        ref = jax.vmap(
            lambda c: jax.ops.segment_sum(
                c, dst, num_segments=n, indices_are_sorted=True
            )
        )(cand)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-9
        )


def test_relax_sum_combine_vs_reference_segment_sum():
    """The kernel's sum path is the segment-sum accumulate idiom: against
    the segment_sum oracle on the transposed [E, S] view."""
    from repro.kernels.bfs_relax.ops import _block_dims, relax_blockmap_call
    from repro.graph.structs import block_ranges_for

    rng = np.random.default_rng(3)
    n, e, s = 130, 700, 4
    dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
    cand = jnp.asarray(rng.normal(size=(s, e)), jnp.float32)
    bn, be, _, _ = _block_dims(n, e, 64, 64)
    start, cnt, t_max = block_ranges_for(dst, n, bn, be)
    out = relax_blockmap_call(
        jnp.asarray(start), jnp.asarray(cnt), jnp.asarray(dst),
        cand, jnp.zeros((s, n), jnp.float32),
        reduce="sum", block_n=bn, block_e=be, t_max=t_max, interpret=True,
    )
    ref = reference_segment_sum(jnp.asarray(dst), cand.T, n).T
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


def test_block_dims_degenerate():
    """Sub-block problems (``e < 8``/``n < 8``, including ``e == 0``) must
    round pads up to at least one full block -- a zero-size grid dimension
    would never initialize the output tile."""
    from repro.kernels.bfs_relax.ops import _block_dims

    for n, e in [(1, 0), (1, 1), (5, 3), (7, 0), (300, 1), (1, 300)]:
        bn, be, n_pad, e_pad = _block_dims(n, e, 512, 512)
        assert e_pad >= be > 0 and e_pad % be == 0, (n, e)
        assert n_pad >= bn > 0 and n_pad % bn == 0, (n, e)
        assert n_pad >= n and e_pad >= e, (n, e)


def test_relax_fn_single_edge_graph():
    from repro.graph.program import SsspProgram

    prog = SsspProgram()
    lay = dst_sorted_layout(
        3, np.array([0], np.int32), np.array([2], np.int32),
        np.array([1.5], np.float32),
    )
    state = jnp.asarray([[0.0, np.inf, np.inf]], jnp.float32)
    frontier = jnp.asarray([[True, False, False]])
    out = engine_relax(state, frontier, lay, program=prog)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray([[0.0, np.inf, 1.5]], np.float32)
    )


def test_relax_fn_empty_edge_set_and_frontier():
    """e == 0 returns the combine identity without launching a kernel; an
    empty frontier feeds all-identity candidates and must be a no-op for
    min programs."""
    from repro.graph.program import PageRankProgram, SsspProgram
    from repro.kernels.bfs_relax import make_relax_fn

    empty = dst_sorted_layout(
        4, np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0, np.float32)
    )
    state = jnp.asarray([[1.0, 2.0, 3.0, 4.0]], jnp.float32)
    fr = jnp.ones((1, 4), bool)
    np.testing.assert_array_equal(
        np.asarray(engine_relax(state, fr, empty, program=SsspProgram())),
        np.asarray(state),
    )
    np.testing.assert_array_equal(
        np.asarray(
            engine_relax(state, fr, empty, program=PageRankProgram())
        ),
        np.zeros((1, 4), np.float32),
    )
    # make_relax_fn's e == 0 relax is the combine identity too
    fn, blockmap = make_relax_fn(np.zeros(0, np.int32), 4, reduce="min")
    assert blockmap == ()
    np.testing.assert_array_equal(
        np.asarray(fn(blockmap, jnp.zeros((1, 0)), state)), np.asarray(state)
    )

    # non-empty edges, empty frontier: min pass returns state unchanged
    lay = dst_sorted_layout(
        4, np.array([0, 1], np.int32), np.array([1, 2], np.int32),
        np.ones(2, np.float32),
    )
    out = engine_relax(
        state, jnp.zeros((1, 4), bool), lay, program=SsspProgram()
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(state))
