"""Streaming graph mutations (``graph.deltas``), the incremental
repartitioner (``core.repartition``), and the unified session/config API
(``graph.session`` / ``graph.config``).

The load-bearing invariants:
  * merge(deltas) == from-scratch: ``merged_mesh_layout`` is byte-identical,
    field by field, to rebuilding the mutated graph's layout from nothing
    (property-tested over random graphs, buffers, device maps and mirror
    thresholds),
  * the bounded LPA repartitioner never worsens the mirror-aware partition
    penalty, strictly improves it when it moves anything, respects the
    balance cap, and converges to a fixpoint on the ragged P=5 graph,
  * ``GraphSession.apply_deltas`` carries in-flight dense window state
    bit-identically and reactivates inserted-edge sources, so a continued
    monotone traversal lands exactly on the mutated graph's fixpoint,
  * the elastic executor and the serving layer interleave mutations with
    traffic and record them in their reports,
  * ``EngineConfig`` is the one spelling of every engine knob: equal
    configs share a cached engine, and the bare knob keywords are unknown
    to every entry point,
  * every report type shares the schema-versioned ``asdict()`` surface.
"""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.repartition import (
    RepartitionConfig,
    incremental_repartition,
    partition_penalty,
)
from repro.graph.config import REPORT_SCHEMA_VERSION, EngineConfig
from repro.graph.deltas import (
    DeltaBufferFull,
    EdgeDeltaBuffer,
    apply_delta_buffer,
    merged_mesh_layout,
)
from repro.graph.generators import erdos_renyi_graph, rmat_graph, weighted
from repro.graph.partition import (
    bfs_grow_partition,
    contiguous_device_map,
    mesh_edge_layout,
    mesh_layout_key,
)
from repro.graph.program import PageRankProgram, SsspProgram
from repro.graph.session import open_session
from repro.graph.structs import MeshEdgeLayout, PartitionedGraph
from repro.graph.traversal import get_engine


def _ragged_pg(seed: int = 7, *, with_weights: bool = False):
    """The suite's ragged case: 400 vertices over P=5 partitions."""
    g = erdos_renyi_graph(400, 4.0, seed=seed)
    pg = bfs_grow_partition(g, 5, seed=2)
    if with_weights:
        pg = PartitionedGraph(
            weighted(g, seed=4), pg.n_parts, pg.part_of_vertex
        )
    return pg


def _assert_layouts_identical(a: MeshEdgeLayout, b: MeshEdgeLayout, ctx=""):
    for f in dataclasses.fields(MeshEdgeLayout):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            assert x.dtype == y.dtype, f"{ctx}{f.name}: dtype {x.dtype} != {y.dtype}"
            np.testing.assert_array_equal(x, y, err_msg=f"{ctx}{f.name}")
        else:
            assert x == y, f"{ctx}{f.name}: {x} != {y}"


# -- merge(deltas) == from-scratch build (the tentpole invariant) -------------


@st.composite
def merge_cases(draw):
    seed = draw(st.integers(0, 2**16))
    n_parts = draw(st.sampled_from([3, 5, 8]))
    d_n = draw(st.sampled_from([2, 4]))
    mirror = draw(st.sampled_from([None, 2]))
    use_weights = draw(st.booleans())
    n_ins = draw(st.integers(1, 24))
    n_del = draw(st.integers(0, 6))
    return seed, n_parts, d_n, mirror, use_weights, n_ins, n_del


@given(merge_cases())
@settings(max_examples=20, deadline=None)
def test_merged_layout_byte_identical_to_scratch(case):
    seed, n_parts, d_n, mirror, use_weights, n_ins, n_del = case
    rng = np.random.default_rng(seed)
    g = rmat_graph(8, 4, seed=seed % 97)
    if use_weights:
        g = weighted(g, seed=seed % 89)
    pg = bfs_grow_partition(g, n_parts, seed=1)
    n = g.n_vertices

    buf = EdgeDeltaBuffer()
    for _ in range(n_ins):
        u, v = int(rng.integers(n)), int(rng.integers(n))
        buf.insert(u, v, float(rng.uniform(0.1, 2.0)) if use_weights else None)
    if n_del:
        # only edges with a single parallel copy delete unambiguously here
        key = g.src.astype(np.int64) * n + g.dst
        uniq, counts = np.unique(key, return_counts=True)
        singles = uniq[counts == 1]
        take = singles[rng.choice(singles.size, size=min(n_del, singles.size),
                                  replace=False)]
        buf.delete_many((take // n).astype(np.int64),
                        (take % n).astype(np.int64))

    dmap = contiguous_device_map(n_parts, d_n)
    old_layout = mesh_edge_layout(pg, dmap, d_n, mirror_degree=mirror)
    new_pg = apply_delta_buffer(pg, buf)
    merged = merged_mesh_layout(pg, new_pg, old_layout)
    # a second fresh apply gives a graph with cold caches: truly from scratch
    scratch = mesh_edge_layout(
        apply_delta_buffer(pg, buf), dmap, d_n, mirror_degree=mirror
    )
    _assert_layouts_identical(merged, scratch, ctx=f"seed={seed} ")
    # and the merged layout is primed into new_pg's cache under the
    # canonical key -- the next engine must adopt it, not rebuild
    assert mesh_edge_layout(new_pg, dmap, d_n, mirror_degree=mirror) is merged


def test_delta_generation_threads_into_layout_keys():
    pg = _ragged_pg()
    buf = EdgeDeltaBuffer()
    buf.insert(0, 1)
    pg1 = apply_delta_buffer(pg, buf)
    pg2 = apply_delta_buffer(pg1, buf)
    assert pg.__dict__.get("_delta_generation", 0) == 0
    assert pg1.__dict__["_delta_generation"] == 1
    assert pg2.__dict__["_delta_generation"] == 2
    dmap = contiguous_device_map(5, 2)
    keys = {mesh_layout_key(dmap, 2, g) for g in (0, 1, 2)}
    assert len(keys) == 3, "generation must separate otherwise-equal keys"
    assert mesh_edge_layout(pg1, dmap, 2).delta_generation == 1


def test_buffer_validation_and_capacity():
    pg = _ragged_pg()
    n = pg.graph.n_vertices

    buf = EdgeDeltaBuffer(capacity=2)
    buf.insert(0, 1)
    buf.delete(int(pg.graph.src[0]), int(pg.graph.dst[0]))
    with pytest.raises(DeltaBufferFull):
        buf.insert(2, 3)

    oob = EdgeDeltaBuffer()
    oob.insert(0, n)  # staged fine; validated at apply time
    with pytest.raises(ValueError, match="outside"):
        apply_delta_buffer(pg, oob)

    key = pg.graph.src.astype(np.int64) * n + pg.graph.dst
    missing = next(k for k in range(n * n) if k not in set(key.tolist()))
    absent = EdgeDeltaBuffer()
    absent.delete(missing // n, missing % n)
    with pytest.raises(ValueError, match="absent"):
        apply_delta_buffer(pg, absent)

    wbuf = EdgeDeltaBuffer()
    wbuf.insert(0, 1, 2.5)
    with pytest.raises(ValueError, match="unweighted"):
        apply_delta_buffer(pg, wbuf)  # unweighted graph, explicit weight

    empty = EdgeDeltaBuffer()
    assert apply_delta_buffer(pg, empty) is pg


# -- incremental repartitioner: monotone, bounded, convergent -----------------


def _migrant_buffer(pg, n_migrants: int, k_edges: int, seed: int):
    """Each migrant gains ``k_edges`` edges (both ways) into one far
    partition -- the workload the neighbor-majority vote must fix."""
    rng = np.random.default_rng(seed)
    part = pg.part_of_vertex
    n = pg.graph.n_vertices
    buf = EdgeDeltaBuffer()
    for v in rng.choice(n, size=n_migrants, replace=False):
        target = (int(part[v]) + 1 + int(rng.integers(pg.n_parts - 1))) % pg.n_parts
        pool = np.flatnonzero(part == target)
        for u in rng.choice(pool, size=min(k_edges, pool.size), replace=False):
            buf.insert(int(v), int(u))
            buf.insert(int(u), int(v))
    return buf


def test_repartitioner_monotone_and_convergent_on_ragged_p5():
    pg = _ragged_pg()
    mutated = apply_delta_buffer(pg, _migrant_buffer(pg, 12, 10, seed=3))
    cfg = RepartitionConfig(max_moves=32, balance=1.25)
    cap = int(np.ceil(cfg.balance * mutated.graph.n_vertices / mutated.n_parts))

    penalties = [int(partition_penalty(mutated.graph, mutated.part_of_vertex))]
    cur = mutated
    for _ in range(20):  # fixpoint: a pass that moves nothing
        rep = incremental_repartition(cur, config=cfg)
        assert rep.penalty_before == penalties[-1]
        assert rep.penalty_after <= rep.penalty_before
        assert (rep.penalty_after < rep.penalty_before) == (rep.moves > 0)
        assert rep.moves <= cfg.max_moves
        assert int(np.bincount(rep.pg.part_of_vertex).max()) <= cap
        penalties.append(int(rep.penalty_after))
        cur = rep.pg
        if rep.moves == 0:
            break
    assert rep.moves == 0, "repartitioner failed to converge in 20 passes"
    assert penalties[-1] < penalties[0], "migrant workload never improved"
    assert penalties == sorted(penalties, reverse=True)  # monotone

    # mirror-aware penalty: hub fan-in collapses to one unit per
    # (src partition, hub), so it can only shrink the plain cut
    plain = partition_penalty(mutated.graph, mutated.part_of_vertex)
    hubbed = partition_penalty(
        mutated.graph, mutated.part_of_vertex, mirror_degree=2
    )
    assert hubbed <= plain


# -- session merges: exact state carry + reactivation (dense path) ------------


def test_session_dense_merge_carries_state_to_mutated_fixpoint():
    pg = _ragged_pg(with_weights=True)
    n = pg.graph.n_vertices
    buf = EdgeDeltaBuffer()
    rng = np.random.default_rng(5)
    for v in rng.choice(n, size=10, replace=False):
        buf.insert(int(v), int((v + n // 2) % n), 0.25)  # shortcuts

    sess = open_session(pg, EngineConfig(m_max=64))
    state = sess.init_state([0, 17])
    w = sess.run_window(state, 3)
    pre_dist = sess.gather_global(w.state.dist)

    state = sess.apply_deltas(buf, state=w.state)
    np.testing.assert_array_equal(sess.gather_global(state.dist), pre_dist)

    for _ in range(64):
        w = sess.run_window(state, 4)
        state = w.state
        if w.done.all():
            break
    assert w.done.all()
    fresh = sess.run(sources=[0, 17])
    np.testing.assert_array_equal(sess.gather_global(state.dist), fresh.dist)
    base = get_engine(pg, config=EngineConfig(m_max=64)).run([0, 17])
    assert not np.array_equal(np.asarray(fresh.dist), np.asarray(base.dist)), (
        "shortcut inserts changed nothing -- reactivation untested"
    )


def test_session_merge_guards():
    pg = _ragged_pg()
    sess = open_session(pg, EngineConfig(m_max=64))
    state = sess.run_window(sess.init_state([0]), 2).state

    dbuf = EdgeDeltaBuffer()
    dbuf.delete(int(pg.graph.src[0]), int(pg.graph.dst[0]))
    with pytest.raises(ValueError, match="delete"):
        sess.apply_deltas(dbuf, state=state)
    assert sess.pg is pg, "failed merge must not swap the session graph"

    sbuf = EdgeDeltaBuffer()
    sbuf.insert(0, 1)
    pr_state = sess.init_state([0], program=PageRankProgram(num_iters=4))
    with pytest.raises(ValueError, match="stationary"):
        sess.apply_deltas(
            sbuf, state=pr_state, program=PageRankProgram(num_iters=4)
        )

    # stateless delete merges are fine
    assert sess.apply_deltas(dbuf) is None
    assert sess.pg is not pg
    assert sess.pg.graph.n_edges < pg.graph.n_edges

    # and a session-level repartition adopts the improved map
    rep = sess.repartition(RepartitionConfig(max_moves=16, balance=1.25))
    assert rep.penalty_after <= rep.penalty_before
    assert sess.pg is rep.pg


# -- executor + service: mutations interleaved with work ----------------------


def test_executor_mutations_reach_mutated_fixpoint():
    from repro.core.billing import BillingModel, evaluate  # noqa: F401
    from repro.core.placement import ffd_placement
    from repro.core.timing import TimeFunction
    from repro.graph.bsp import run_sssp

    pg = _ragged_pg()
    muts = [(1, _migrant_buffer(pg, 8, 8, seed=9))]
    _, trace = run_sssp(pg, 0, collect_subgraphs=False)
    plan = ffd_placement(TimeFunction.from_trace(trace))

    sess = open_session(pg, EngineConfig(window=1))
    for rcfg in (None, RepartitionConfig(max_moves=32, balance=1.25)):
        ex = sess.executor()
        rep = ex.run(0, plan, mutations=muts, repartition=rcfg)
        assert rep.mutations_applied == 1
        assert ex.pg is not pg
        if rcfg is None:
            assert rep.repartition_moves == 0
            assert np.array_equal(ex.pg.part_of_vertex, pg.part_of_vertex)
        else:
            assert rep.repartition_moves > 0
        fresh = get_engine(ex.pg, config=EngineConfig(m_max=256)).run([0])
        np.testing.assert_array_equal(rep.dist, fresh.dist[0])
        d = rep.asdict()
        assert d["schema_version"] == REPORT_SCHEMA_VERSION
        assert d["kind"] == "execution_report"
        assert d["mutations_applied"] == 1


def test_service_interleaves_mutations_with_queries():
    from repro.serve import ServiceConfig, TraversalService, poisson_trace

    pg = _ragged_pg()
    cfg = ServiceConfig(s_batch=4, window=8, tau_scale=1e3)
    trace = poisson_trace(30, 10.0, pg.graph.n_vertices, seed=0)
    t_mid = trace[len(trace) // 2][0]  # trace rows are (arrival, query)
    buf = _migrant_buffer(pg, 6, 6, seed=11)

    svc = TraversalService(pg, config=cfg)
    rep = svc.run(trace, mutations=[(t_mid, buf)])
    assert rep.mutations_applied == 1
    assert rep.completed == 30
    assert svc.pg is not pg and svc.pg.graph.n_edges > pg.graph.n_edges

    # replay determinism survives the mutation seam
    rep2 = TraversalService(pg, config=cfg).run(trace, mutations=[(t_mid, buf)])
    assert rep == rep2

    d = rep.asdict()
    assert d["schema_version"] == REPORT_SCHEMA_VERSION
    assert d["kind"] == "service_report"
    assert d["mutations_applied"] == 1


# -- the unified config surface: one spelling, cached by value --------------


def test_engine_cache_keys_on_config():
    """Equal ``EngineConfig`` values resolve to one cached engine with equal
    results; a config differing in a keyed knob builds a new engine; no
    config means ``EngineConfig()``."""
    pg = _ragged_pg()
    first = get_engine(pg, config=EngineConfig(m_max=64))
    assert get_engine(pg, config=EngineConfig(m_max=64)) is first
    assert get_engine(pg, config=EngineConfig(m_max=32)) is not first
    assert get_engine(pg) is get_engine(pg, config=EngineConfig())

    res_a = get_engine(
        pg, program=SsspProgram(), config=EngineConfig(m_max=64)
    ).run([0])
    res_b = get_engine(
        pg, program=SsspProgram(), config=EngineConfig(m_max=64)
    ).run([0])
    np.testing.assert_array_equal(res_a.dist, res_b.dist)


def _engine_entry_call(entry: str, pg):
    """A call of one engine entry point with a bare knob keyword."""
    from repro.core.elastic import ElasticBSPExecutor
    from repro.graph.traversal import TraversalEngine
    from repro.serve import ServiceConfig, TraversalService

    if entry == "TraversalEngine":
        return lambda: TraversalEngine(pg, m_max=64)
    if entry == "get_engine":
        return lambda: get_engine(pg, backend="xla")
    if entry == "ElasticBSPExecutor":
        return lambda: ElasticBSPExecutor(pg, mirror_degree=2)
    if entry == "ElasticBSPExecutor.run":
        ex = ElasticBSPExecutor(pg, config=EngineConfig(m_max=64))
        return lambda: ex.run(0, None, window=2)
    assert entry == "TraversalService"
    return lambda: TraversalService(pg, config=ServiceConfig(), mesh=None)


@pytest.mark.parametrize(
    "entry",
    ["TraversalEngine", "get_engine", "ElasticBSPExecutor",
     "ElasticBSPExecutor.run", "TraversalService"],
)
def test_bare_engine_knob_is_unknown_keyword(entry):
    """Knobs are set through ``EngineConfig`` only: a bare knob keyword is
    a ``TypeError`` at every entry point."""
    call = _engine_entry_call(entry, _ragged_pg())
    with pytest.raises(TypeError, match="unexpected keyword"):
        call()


def test_traversal_result_asdict_schema():
    pg = _ragged_pg()
    res = get_engine(pg, config=EngineConfig(m_max=64)).run([0])
    d = res.asdict()
    assert d["schema_version"] == REPORT_SCHEMA_VERSION
    assert d["kind"] == "traversal_result"
    np.testing.assert_array_equal(d["dist"], res.dist)
