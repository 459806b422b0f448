"""Device-resident traversal engine + static-CSR relax kernel regression.

Sweeps the block-skipping kernel (interpret mode, through the dense engine's
``make_relax_fn`` entry) against the pure-jnp oracle and the engine against
the host Bellman-Ford oracle, on random ragged sizes -- n/e odd with respect
to the block sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import bfs_grow_partition, erdos_renyi_graph, hash_partition
from repro.graph.bsp import concat_traces, run_bc_forward, run_sssp
from repro.graph.config import EngineConfig
from repro.graph.generators import weighted
from repro.graph.partition import partitioned_edge_layout
from repro.graph.program import BUILTIN_PROGRAMS, PageRankProgram, SsspProgram
from repro.graph.structs import Graph, PartitionedGraph, dst_sorted_layout
from repro.graph.traversal import (
    TraversalNotConverged,
    get_engine,
    make_superstep_fn,
    plane_arrays,
    reference_bfs,
    reference_pagerank,
    reference_sssp,
    reference_wcc,
)
from repro.kernels.bfs_relax import reference_bfs_relax

from _relax_entry import engine_relax

RAGGED_SIZES = [
    # (n, e) deliberately not multiples of the 64-block sizes used below
    (100, 300),
    (257, 1023),
    (512, 2048),
    (1000, 333),
    (65, 65),
    (7, 5),
]
#: (n, e, seed): every size under one seed, the first four under a second
RAGGED_CASES = [(n, e, n * 31 + e) for n, e in RAGGED_SIZES] + [
    (n, e, e * 17 + n) for n, e in RAGGED_SIZES[:4]
]


def _random_relax_inputs(n, e, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = rng.uniform(0.5, 2.0, e).astype(np.float32)
    dist = np.where(rng.random(n) < 0.5, rng.uniform(0, 10, n), np.inf).astype(
        np.float32
    )
    frontier = rng.random(n) < 0.3
    return src, dst, w, dist, frontier


@pytest.mark.parametrize("case", RAGGED_CASES)
def test_relax_fn_matches_reference_ragged(case):
    n, e, seed = case
    src, dst, w, dist, frontier = _random_relax_inputs(n, e, seed=seed)
    layout = dst_sorted_layout(n, src, dst, w)
    out = engine_relax(
        jnp.asarray(dist), jnp.asarray(frontier), layout,
        block_n=64, block_e=64,
    )
    ref = reference_bfs_relax(
        jnp.asarray(dist), jnp.asarray(frontier),
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_relax_fn_batched_matches_per_source():
    n, e, s_batch = 203, 611, 5
    rng = np.random.default_rng(3)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = rng.uniform(0.5, 2.0, e).astype(np.float32)
    layout = dst_sorted_layout(n, src, dst, w)
    dist = jnp.asarray(
        np.where(rng.random((s_batch, n)) < 0.5, rng.uniform(0, 10, (s_batch, n)), np.inf),
        jnp.float32,
    )
    frontier = jnp.asarray(rng.random((s_batch, n)) < 0.3)
    out = engine_relax(dist, frontier, layout, block_n=64, block_e=64)
    for s in range(s_batch):
        ref = reference_bfs_relax(
            dist[s], frontier[s], jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w)
        )
        np.testing.assert_array_equal(np.asarray(out[s]), np.asarray(ref))


@pytest.mark.parametrize("partitioner", [hash_partition, bfs_grow_partition])
def test_batched_engine_bitmatches_oracle_every_source(partitioner):
    """Acceptance: batched engine distances bit-match reference_bfs for
    every source in the batch (unit-weight BFS distances are exact in f32)."""
    g = erdos_renyi_graph(300, 5.0, seed=11)
    pg = partitioner(g, 4)
    sources = [0, 17, 123, 299]
    res = get_engine(pg, config=EngineConfig(m_max=256)).run(sources)
    for i, s in enumerate(sources):
        ref = reference_bfs(pg, s)
        np.testing.assert_array_equal(res.dist[i], ref.astype(np.float32))


def test_batched_engine_weighted_matches_oracle():
    g = weighted(erdos_renyi_graph(250, 5.0, seed=13), seed=2)
    pg = bfs_grow_partition(g, 4, seed=3)
    sources = [1, 42, 200]
    res = get_engine(pg, config=EngineConfig(m_max=256)).run(sources)
    for i, s in enumerate(sources):
        np.testing.assert_allclose(
            res.dist[i], reference_sssp(pg, s), rtol=1e-6
        )


def test_engine_trace_matches_serial_superstep_driver():
    """The device-resident trace must equal a host-driven superstep loop's
    counters row for row (same math, different orchestration)."""
    g = erdos_renyi_graph(220, 4.0, seed=5)
    pg = bfs_grow_partition(g, 3, seed=1)
    source = 7

    superstep = make_superstep_fn(pg)
    n = g.n_vertices
    dist = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
    frontier = jnp.zeros((n,), bool).at[source].set(True)
    rows_e, rows_v, rows_m, iters = [], [], [], []
    while bool(frontier.any()):
        r = superstep(dist, frontier)
        dist, frontier = r.dist, r.next_frontier
        rows_e.append(np.asarray(r.edges_examined))
        rows_v.append(np.asarray(r.verts_processed))
        rows_m.append(np.asarray(r.msgs_sent))
        iters.append(int(r.inner_iters))

    _, trace = run_sssp(pg, source, collect_subgraphs=False)
    np.testing.assert_array_equal(trace.edges_examined, np.stack(rows_e))
    np.testing.assert_array_equal(trace.verts_processed, np.stack(rows_v))
    np.testing.assert_array_equal(trace.msgs_sent, np.stack(rows_m))
    np.testing.assert_array_equal(trace.inner_iters, np.asarray(iters))


def test_bc_forward_batched_equals_serial_waves():
    """run_bc_forward (one batched traversal) must produce the same
    concatenated trace as independent per-source runs."""
    g = erdos_renyi_graph(180, 4.0, seed=9)
    pg = bfs_grow_partition(g, 4, seed=2)
    sources = [0, 50, 99, 150]
    batched = run_bc_forward(pg, sources, max_supersteps=128)
    serial = concat_traces(
        [
            run_sssp(pg, s, max_supersteps=128, collect_subgraphs=False)[1]
            for s in sources
        ]
    )
    np.testing.assert_array_equal(batched.active, serial.active)
    np.testing.assert_array_equal(batched.edges_examined, serial.edges_examined)
    np.testing.assert_array_equal(batched.verts_processed, serial.verts_processed)
    np.testing.assert_array_equal(batched.msgs_sent, serial.msgs_sent)
    np.testing.assert_array_equal(batched.inner_iters, serial.inner_iters)


def test_engine_raises_on_superstep_cap():
    g = erdos_renyi_graph(200, 4.0, seed=21)
    pg = hash_partition(g, 4)
    with pytest.raises(RuntimeError, match="did not converge"):
        get_engine(pg, config=EngineConfig(m_max=2)).run([0])


def test_non_convergence_error_reports_steps_and_keeps_partial_result():
    """The cap error must name the per-source n_supersteps and carry the
    partial TraversalResult instead of discarding it."""
    g = erdos_renyi_graph(200, 4.0, seed=21)
    pg = hash_partition(g, 4)
    with pytest.raises(TraversalNotConverged, match=r"n_supersteps=\[2\]") as ei:
        get_engine(pg, config=EngineConfig(m_max=2)).run([0])
    partial = ei.value.result
    assert np.array_equal(partial.n_supersteps, [2])
    # two supersteps of real progress are retained
    assert np.isfinite(partial.dist).sum() > 1
    assert partial.frontier.any()


def test_run_window_chaining_matches_single_run():
    """Chained run_window calls must reproduce run()'s distances, counters,
    and superstep counts exactly, for several window sizes."""
    g = erdos_renyi_graph(300, 5.0, seed=11)
    pg = bfs_grow_partition(g, 4, seed=1)
    eng = get_engine(pg, config=EngineConfig(m_max=256))
    sources = [0, 17, 123]
    full = eng.run(sources)
    for k in (1, 3, 7, 64):
        state = eng.init_state(sources)
        chunks = []
        for _ in range(256):
            w = eng.run_window(state, k)
            state = w.state
            chunks.append(w)
            if w.done.all():
                break
        assert chunks[-1].done.all()  # no convergence raise mid-run
        we = np.concatenate([c.edges_examined for c in chunks], axis=1)
        wv = np.concatenate([c.verts_processed for c in chunks], axis=1)
        m = we.shape[1]
        np.testing.assert_array_equal(we, full.edges_examined[:, :m])
        np.testing.assert_array_equal(wv, full.verts_processed[:, :m])
        np.testing.assert_array_equal(np.asarray(state.dist), full.dist)
        np.testing.assert_array_equal(
            np.asarray(state.n_supersteps), full.n_supersteps
        )


def test_run_window_reports_next_active_partitions():
    """part_active_next must equal the partition set holding next-frontier
    vertices (what the elastic executor's placement decision consumes)."""
    g = erdos_renyi_graph(250, 4.0, seed=3)
    pg = bfs_grow_partition(g, 5, seed=2)
    eng = get_engine(pg, config=EngineConfig(m_max=256))
    state = eng.init_state([0])
    w = eng.run_window(state, 1)
    frontier = np.asarray(w.state.frontier[0])
    expect = np.zeros(pg.n_parts, dtype=bool)
    for p in np.unique(pg.part_of_vertex[np.flatnonzero(frontier)]):
        expect[p] = True
    np.testing.assert_array_equal(w.part_active_next[0], expect)
    assert not w.done[0]


def test_active_subgraph_sets_from_device_counters():
    """collect_subgraphs must reproduce the host-side definition: the set of
    subgraphs holding frontier vertices at superstep start."""
    g = erdos_renyi_graph(240, 4.0, seed=7)
    pg = bfs_grow_partition(g, 4, seed=4)
    dist, trace = run_sssp(pg, 0)
    assert len(trace.active_subgraphs) == trace.n_supersteps
    # superstep 0: exactly the source's subgraph
    np.testing.assert_array_equal(
        trace.active_subgraphs[0], [pg.subgraph_of_vertex[0]]
    )
    # active subgraphs always live in active partitions
    for s in range(trace.n_supersteps):
        parts = set(np.flatnonzero(trace.active[s]).tolist())
        assert {
            int(pg.part_of_subgraph[sg]) for sg in trace.active_subgraphs[s]
        } == parts


@pytest.mark.parametrize("name", ["bfs", "sssp", "wcc", "pagerank"])
def test_dense_engine_backend_parity(name):
    """pallas-interpret == xla on the dense engine: counters bit-identical
    for every program (they stay on XLA), state bit-identical for min
    programs and allclose for the float sum path."""
    g = weighted(erdos_renyi_graph(250, 4.0, seed=3), seed=1)
    pg = bfs_grow_partition(g, 4)
    srcs = [0, 100]
    ctor = BUILTIN_PROGRAMS[name]
    rx = get_engine(
        pg, program=ctor(), config=EngineConfig(m_max=64, backend="xla"),
    ).run(srcs)
    rk = get_engine(
        pg,
        program=ctor(),
        config=EngineConfig(m_max=64, backend="pallas-interpret"),
    ).run(srcs)
    for f in ("edges_examined", "verts_processed", "msgs_sent",
              "inner_iters", "wire_msgs", "n_supersteps"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rx, f)), np.asarray(getattr(rk, f)), err_msg=f
        )
    if ctor().reduce == "min":
        np.testing.assert_array_equal(np.asarray(rx.dist), np.asarray(rk.dist))
    else:
        np.testing.assert_allclose(
            np.asarray(rk.dist), np.asarray(rx.dist), rtol=1e-5, atol=1e-9
        )


def test_engine_rejects_unknown_backend():
    pg = hash_partition(erdos_renyi_graph(50, 3.0, seed=0), 2)
    with pytest.raises(ValueError, match="backend"):
        get_engine(pg, config=EngineConfig(backend="cuda"))


def _graph_with_duplicates_and_isolated(seed: int = 4) -> Graph:
    """Weighted directed arcs over vertices 0..139, 120 of them repeated
    (with their own weights); vertices 140..159 have no arc at all."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, 140, 700)
    dst = (src + rng.integers(1, 140, 700)) % 140
    again = rng.choice(700, 120, replace=False)
    src = np.concatenate([src, src[again]]).astype(np.int32)
    dst = np.concatenate([dst, dst[again]]).astype(np.int32)
    w = rng.uniform(1.0, 4.0, src.shape[0]).astype(np.float32)
    return Graph(160, src, dst, w)


def _per_arc_counts(pg: PartitionedGraph, frontiers) -> tuple:
    """(edges_examined, verts_processed) of one frontier sequence, counted
    per arc and per vertex with ``np.bincount``."""
    lay = partitioned_edge_layout(pg)
    p = pg.n_parts
    we = sum(
        np.bincount(lay.local_part[f[lay.local.src]], minlength=p)
        for f in frontiers
    )
    wv = sum(
        np.bincount(pg.part_of_vertex[f], minlength=p) for f in frontiers
    )
    return we, wv


def _sssp_counter_rows(pg: PartitionedGraph, source: int) -> list:
    """Host replay of the monotone window's frontier history for one SSSP
    source: per superstep, the closure loop's frontiers give the local
    counters and the superstep's touched set gives ``msgs_sent``."""
    lay = partitioned_edge_layout(pg)
    loc, rem = lay.local, lay.remote
    p = pg.n_parts
    d = np.full(pg.graph.n_vertices, np.inf, np.float32)
    d[source] = 0.0
    fr = d == 0.0
    rows = []
    while fr.any():
        frontiers, touched, f = [], fr.copy(), fr
        while f.any():
            frontiers.append(f)
            new = d.copy()
            act = f[loc.src]
            np.minimum.at(new, loc.dst[act], d[loc.src[act]] + loc.weights[act])
            f = new < d
            touched |= f
            d = new
        we, wv = _per_arc_counts(pg, frontiers)
        ms = np.bincount(lay.remote_src_part[touched[rem.src]], minlength=p)
        new = d.copy()
        act = touched[rem.src]
        np.minimum.at(new, rem.dst[act], d[rem.src[act]] + rem.weights[act])
        fr = new < d
        d = new
        rows.append((we, wv, ms))
    return rows


@pytest.mark.parametrize("graph", ["erdos_renyi", "duplicates_isolated"])
@pytest.mark.parametrize("partitioner", [hash_partition, bfs_grow_partition])
@pytest.mark.parametrize("name", ["sssp", "pagerank"])
def test_dense_counters_equal_per_arc_bincounts(name, partitioner, graph):
    """The dense window takes its counters from per-vertex degrees; they must
    equal per-arc ``np.bincount`` counts of the frontier history, duplicate
    arcs and arc-less vertices included."""
    if graph == "erdos_renyi":
        g = weighted(erdos_renyi_graph(200, 4.0, seed=8), seed=5)
    else:
        g = _graph_with_duplicates_and_isolated()
    pg = partitioner(g, 4)
    lay = partitioned_edge_layout(pg)
    if name == "pagerank":
        # stationary: one pass per superstep, so the history is the carried
        # frontier at each one-superstep window
        eng = get_engine(
            pg, program=PageRankProgram(num_iters=6),
            config=EngineConfig(m_max=16),
        )
        state = eng.init_state([0, 0])
        n_windows = 0
        while True:
            fr = np.asarray(state.frontier)
            w = eng.run_window(state, 1)
            for i in range(fr.shape[0]):
                we, wv = _per_arc_counts(pg, [fr[i]])
                ms = np.bincount(
                    lay.remote_src_part[fr[i][lay.remote.src]],
                    minlength=pg.n_parts,
                )
                np.testing.assert_array_equal(w.edges_examined[i, 0], we)
                np.testing.assert_array_equal(w.verts_processed[i, 0], wv)
                np.testing.assert_array_equal(w.msgs_sent[i, 0], ms)
            n_windows += 1
            state = w.state
            if w.done.all():
                break
        assert n_windows == 6
        return
    sources = [0, 77, 150]
    res = get_engine(
        pg, program=SsspProgram(), config=EngineConfig(m_max=64)
    ).run(sources)
    for i, s in enumerate(sources):
        expect = _sssp_counter_rows(pg, s)
        m = len(expect)
        assert int(res.n_supersteps[i]) == m
        for f, j in (("edges_examined", 0), ("verts_processed", 1),
                     ("msgs_sent", 2)):
            got = np.asarray(getattr(res, f))[i]
            np.testing.assert_array_equal(
                got[:m], np.stack([r[j] for r in expect]), err_msg=f
            )
            assert not got[m:].any(), f


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("name", sorted(BUILTIN_PROGRAMS))
def test_dense_window_has_no_partition_scatter(name, backend):
    """No scatter into ``n_parts`` bins is left in the dense window: every
    per-partition counter is a dense reduction over the vertex axis.  The
    ``[n]``-sized value reductions of the xla backend stay scatters."""
    from repro.analysis.jaxpr_audit import iter_eqns

    g = weighted(erdos_renyi_graph(120, 4.0, seed=2), seed=3)
    pg = bfs_grow_partition(g, 5)
    eng = get_engine(
        pg, program=BUILTIN_PROGRAMS[name](),
        config=EngineConfig(m_max=8, backend=backend),
    )
    shapes = [
        tuple(v.aval.shape)
        for eqn, _ in iter_eqns(eng.window_jaxpr(k=3, s_batch=2).jaxpr)
        if "scatter" in eqn.primitive.name
        for v in eqn.outvars
    ]
    assert not [s for s in shapes if s and s[-1] == pg.n_parts], shapes
    if backend == "xla":
        assert (2, g.n_vertices) in shapes  # the value reductions remain


def _unit_interval_graph(seed: int = 7) -> Graph:
    """An ER graph with weights uniform in [0, 1), Graph500 kernel 3's
    distribution, equal for ``(u, v)`` and ``(v, u)``; a fifth of the
    edges weigh exactly 0."""
    g = erdos_renyi_graph(300, 5.0, seed=seed)
    pair = (np.minimum(g.src, g.dst).astype(np.int64) * g.n_vertices
            + np.maximum(g.src, g.dst))
    _, edge = np.unique(pair, return_inverse=True)
    rng = np.random.default_rng(seed)
    w = rng.random(edge.max() + 1).astype(np.float32)
    w[rng.random(w.size) < 0.2] = 0.0
    return Graph(g.n_vertices, g.src, g.dst, w[edge])


@pytest.mark.parametrize("partitioner", [hash_partition, bfs_grow_partition])
def test_sssp_on_unit_interval_weights_with_zeros(partitioner):
    """Weighted SSSP over [0, 1) weights, zeros included: the local closure
    improves reached vertices again, and the answer agrees with float64
    Bellman-Ford and scipy's Dijkstra to float32 rounding, is an exact
    float32 fixpoint, and is bit-identical between the backends."""
    from scipy import sparse
    from scipy.sparse import csgraph

    from bench.reference import sssp_fixpoint_violations

    g = _unit_interval_graph()
    assert (g.edge_weights == 0).any()
    pg = partitioner(g, 4)
    sources = [0, 17, 123, 299]
    runs = {
        backend: get_engine(
            pg,
            program=SsspProgram(),
            config=EngineConfig(m_max=256, backend=backend),
        ).run(sources)
        for backend in ("xla", "pallas-interpret")
    }
    rx, rk = runs["xla"], runs["pallas-interpret"]
    for f in ("dist", "inner_iters", "verts_processed", "n_supersteps"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rx, f)), np.asarray(getattr(rk, f)), err_msg=f
        )
    adj = sparse.csr_matrix(
        (g.edge_weights.astype(np.float64), (g.src, g.dst)),
        shape=(g.n_vertices, g.n_vertices),
    )  # explicit zeros stay edges in scipy's csgraph
    want = csgraph.dijkstra(adj, directed=True, indices=sources)
    dist = np.asarray(rk.dist)
    for i, s in enumerate(sources):
        np.testing.assert_allclose(dist[i], reference_sssp(pg, s), rtol=1e-6)
        np.testing.assert_allclose(dist[i], want[i], rtol=1e-6)
        # some vertex entered the frontier again after it was reached
        reached = int(np.isfinite(dist[i]).sum())
        assert int(np.asarray(rk.verts_processed)[i].sum()) > reached
    assert not sssp_fixpoint_violations(g.src, g.dst, g.edge_weights, sources,
                                        dist).any()
    assert ((dist == 0).sum(axis=1) > 1).any()  # a path of zero weights


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("name", sorted(BUILTIN_PROGRAMS))
def test_dense_window_gathers_once_per_plane(name, backend):
    """Each pass gathers each plane's arcs once: the frontier is folded into
    the state over the vertex axis first, so the window holds one ``[S, E]``
    gather per plane, each reading a materialised buffer (the output of an
    optimisation barrier), and no per-arc gather of the bool frontier."""
    from repro.analysis.jaxpr_audit import iter_eqns

    g = weighted(erdos_renyi_graph(120, 4.0, seed=2), seed=3)
    pg = bfs_grow_partition(g, 5)
    lay = partitioned_edge_layout(pg)
    e_l, e_r = lay.local.src.size, lay.remote.src.size
    assert e_l != e_r  # the planes' gathers are told apart by their width
    eng = get_engine(
        pg, program=BUILTIN_PROGRAMS[name](),
        config=EngineConfig(m_max=8, backend=backend),
    )
    eqns = [e for e, _ in iter_eqns(eng.window_jaxpr(k=3, s_batch=2).jaxpr)]
    barriered = {
        v for e in eqns if e.primitive.name == "optimization_barrier"
        for v in e.outvars
    }
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    widths = sorted(tuple(e.outvars[0].aval.shape) for e in gathers)
    assert widths == sorted([(2, e_l), (2, e_r)]), widths
    assert not [e for e in gathers if e.outvars[0].aval.dtype == np.bool_]
    assert all(e.invars[0] in barriered for e in gathers)


def _unfolded_window(prog, pg, state, k: int) -> dict:
    """Host replay of one ``k``-superstep window of the dense engine over a
    batch, in the per-arc form: an arc's candidate is
    ``relax(state[src], w)`` where its source is in the frontier, and
    ``identity`` elsewhere.  The counters are per-arc ``np.bincount`` counts.
    Returns the window's carried state and counters under their
    ``WindowResult`` names."""
    lay = partitioned_edge_layout(pg)
    loc, rem = lay.local, lay.remote
    planes = [np.asarray(a) for a in plane_arrays(pg, prog)]
    p, n, ident = pg.n_parts, pg.graph.n_vertices, prog.identity
    d, fr, nst = (np.array(a) for a in state)
    s_b = d.shape[0]
    out = {f: np.zeros((s_b, k, p), np.int32)
           for f in ("edges_examined", "verts_processed", "msgs_sent")}
    out["inner_iters"] = np.zeros((s_b, k), np.int32)

    def relax(arcs, w, d, f, base):
        cand = np.where(f[arcs.src], prog.relax(d[arcs.src], w), ident)
        base = base.copy()
        (np.minimum if prog.reduce == "min" else np.add).at(
            base, arcs.dst, cand
        )
        return base

    def count(field, i, s, part_of, mask):
        out[field][i, s] += np.bincount(part_of[mask], minlength=p)

    for s in range(k):
        if not fr.any():
            break
        for i in range(s_b):
            f = fr[i]
            nst[i] += f.any()
            if prog.stationary:
                count("edges_examined", i, s, lay.local_part, f[loc.src])
                count("verts_processed", i, s, pg.part_of_vertex, f)
                count("msgs_sent", i, s, lay.remote_src_part, f[rem.src])
                out["inner_iters"][i, s] = f.any()
                zero = np.zeros_like(d[i])
                acc = (relax(loc, planes[0], d[i], f, zero)
                       + relax(rem, planes[1], d[i], f, zero))
                d[i] = prog.apply(d[i], acc, n)
                fr[i] = f & prog.keep_running(nst[i])
                continue
            touched = f.copy()
            while f.any():
                count("edges_examined", i, s, lay.local_part, f[loc.src])
                count("verts_processed", i, s, pg.part_of_vertex, f)
                out["inner_iters"][i, s] += 1
                new = relax(loc, planes[0], d[i], f, d[i])
                f = prog.is_active(new, d[i])
                touched |= f
                d[i] = new
            count("msgs_sent", i, s, lay.remote_src_part, touched[rem.src])
            new = relax(rem, planes[1], d[i], touched, d[i])
            fr[i] = prog.is_active(new, d[i])
            d[i] = new
    return dict(out, dist=d, frontier=fr, n_supersteps=nst)


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("name", ["bfs", "sssp", "wcc", "pagerank"])
def test_dense_window_fold_matches_per_arc_form(name, backend):
    """Windows of the folded gather equal the per-arc form they replace, in
    state and every counter, over short windows, a row deactivated by
    ``backfill_rows`` and a row whose frontier was thinned, so that vertices
    outside the frontier hold values they have not sent yet.  A fold that
    forgot the frontier would send them.  The rows left alone end at the
    reference answer; for SSSP the per-arc form is also checked against
    ``make_superstep_fn``."""
    g = _unit_interval_graph()
    pg = bfs_grow_partition(g, 4)
    prog = (PageRankProgram(num_iters=6) if name == "pagerank"
            else BUILTIN_PROGRAMS[name]())
    eng = get_engine(
        pg, program=prog, config=EngineConfig(m_max=64, backend=backend)
    )
    sources = [0, 17, 123, 299]
    state = eng.init_state(sources)
    for n_window in range(64):
        if n_window == 1:
            state = eng.backfill_rows(state, [1], [-1])
            state = state._replace(
                frontier=state.frontier.at[0, ::2].set(False)
            )
        want = _unfolded_window(prog, pg, jax.device_get(state), 2)
        w = eng.run_window(state, 2)
        state = w.state
        got = dict(w._asdict(), **jax.device_get(state)._asdict())
        for f, expect in want.items():
            if f == "dist" and prog.reduce == "sum":
                # float sums in another order: the kernel's and numpy's
                np.testing.assert_allclose(
                    got[f], expect, rtol=1e-6, atol=0, err_msg=f
                )
            else:
                np.testing.assert_array_equal(got[f], expect, err_msg=f)
        if name == "sssp" and n_window == 1:
            step = make_superstep_fn(pg)
            host = jax.device_get(state)
            want = _unfolded_window(prog, pg, host, 1)
            for i in range(len(sources)):
                r = step(host.dist[i], host.frontier[i])
                np.testing.assert_array_equal(r.dist, want["dist"][i])
                for f in ("edges_examined", "verts_processed", "msgs_sent",
                          "inner_iters"):
                    np.testing.assert_array_equal(
                        getattr(r, f), want[f][i, 0], err_msg=f
                    )
        if w.done.all():
            break
    assert w.done.all()
    dist = np.asarray(state.dist)
    assert np.isfinite(dist[0]).sum() > 1
    for i in (2, 3):
        if name == "bfs":
            np.testing.assert_array_equal(dist[i], reference_bfs(pg, sources[i]))
        elif name == "sssp":
            np.testing.assert_allclose(
                dist[i], reference_sssp(pg, sources[i]), rtol=1e-6
            )
        elif name == "wcc":
            np.testing.assert_array_equal(dist[i], reference_wcc(pg))
        else:
            np.testing.assert_allclose(
                dist[i], reference_pagerank(pg, num_iters=6), rtol=1e-5
            )
