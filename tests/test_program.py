"""VertexProgram algebra: one engine API for BFS / SSSP / WCC / PageRank.

Dense-path coverage (the mesh twins live in ``tests/_mesh_child.py`` under 8
forced host devices): every builtin program against its numpy reference, BFS
bit-identity through the new API, windowed chaining for the stationary
shape, the elastic executor running source-free programs, program-plane
plumbing, seeded deterministic edge weights, and the spec validation.
"""

import numpy as np
import pytest

from repro.core.elastic import ElasticBSPExecutor
from repro.core.placement import ffd_placement
from repro.core.replan import ReplanConfig
from repro.core.timing import TimeFunction
from repro.graph.bsp import run_program
from repro.graph.config import EngineConfig
from repro.graph.generators import erdos_renyi_graph, weighted
from repro.graph.partition import bfs_grow_partition
from repro.graph.program import (
    BUILTIN_PROGRAMS,
    BfsProgram,
    PageRankProgram,
    SsspProgram,
    VertexProgram,
    WccProgram,
    validate_program,
)
from repro.graph.structs import Graph
from repro.graph.traversal import (
    get_engine,
    plane_arrays,
    reference_bfs,
    reference_pagerank,
    reference_sssp,
    reference_wcc,
)


@pytest.fixture(scope="module")
def pg_weighted():
    g = weighted(erdos_renyi_graph(300, 5.0, seed=11), seed=2)
    return bfs_grow_partition(g, 4, seed=1)


@pytest.fixture(scope="module")
def pg_unweighted():
    g = erdos_renyi_graph(260, 4.0, seed=7)
    return bfs_grow_partition(g, 4, seed=2)


@pytest.fixture(scope="module")
def pg_two_components():
    """Disjoint union of two ER graphs: WCC must find both components."""
    ga = erdos_renyi_graph(140, 3.0, seed=5)
    gb = erdos_renyi_graph(90, 3.0, seed=6)
    src = np.concatenate([ga.src, gb.src + ga.n_vertices]).astype(np.int32)
    dst = np.concatenate([ga.dst, gb.dst + ga.n_vertices]).astype(np.int32)
    g = Graph(ga.n_vertices + gb.n_vertices, src, dst)
    return bfs_grow_partition(g, 3, seed=1)


# -- program-vs-reference correctness (dense engine) --------------------------


def test_bfs_program_ignores_weights(pg_weighted):
    """BfsProgram must produce hop counts even on a weighted graph (the unit
    edge plane overrides the graph weights)."""
    sources = [0, 17, 123]
    res = get_engine(
        pg_weighted, program=BfsProgram(), config=EngineConfig(m_max=256),
    ).run(sources)
    for i, s in enumerate(sources):
        np.testing.assert_array_equal(
            res.dist[i], reference_bfs(pg_weighted, s).astype(np.float32)
        )


def test_sssp_program_matches_weighted_oracle(pg_weighted):
    sources = [1, 42, 200]
    res = get_engine(
        pg_weighted, program=SsspProgram(), config=EngineConfig(m_max=256),
    ).run(sources)
    for i, s in enumerate(sources):
        np.testing.assert_allclose(
            res.dist[i], reference_sssp(pg_weighted, s), rtol=1e-6
        )


def test_wcc_program_labels_components(pg_two_components):
    pg = pg_two_components
    res = get_engine(
        pg, program=WccProgram(), config=EngineConfig(m_max=256),
    ).run([0])
    labels = res.dist[0]
    assert labels.dtype == np.int32  # the program's state spec, not float
    np.testing.assert_array_equal(labels, reference_wcc(pg).astype(np.int32))
    # two components: labels are the min vertex id of each
    assert set(np.unique(labels).tolist()) == {0, 140}


def test_pagerank_program_matches_power_iteration(pg_unweighted):
    prog = PageRankProgram(damping=0.85, num_iters=18)
    res = get_engine(
        pg_unweighted, program=prog, config=EngineConfig(m_max=64),
    ).run([0])
    ref = reference_pagerank(pg_unweighted, 0.85, 18)
    np.testing.assert_allclose(res.dist[0], ref, rtol=1e-5, atol=1e-9)
    assert abs(float(res.dist[0].sum()) - 1.0) < 1e-4
    # the fixed budget is the convergence test: exactly num_iters supersteps
    np.testing.assert_array_equal(res.n_supersteps, [18])


# -- BFS bit-identity through the new API (acceptance, D=1) -------------------


def test_bfs_through_program_api_bit_identical_to_default(pg_unweighted):
    """On an unweighted graph the default engine (SsspProgram over unit
    weights == the pre-algebra engine) and the explicit BfsProgram must agree
    bit-for-bit in state AND every [S, m_max, P] counter buffer."""
    sources = [0, 17, 123, 259]
    r_def = get_engine(pg_unweighted, config=EngineConfig(m_max=256)).run(sources)
    r_bfs = get_engine(
        pg_unweighted, program=BfsProgram(), config=EngineConfig(m_max=256),
    ).run(sources)
    for field in (
        "dist", "n_supersteps", "edges_examined", "verts_processed",
        "msgs_sent", "inner_iters", "wire_msgs",
    ):
        np.testing.assert_array_equal(
            getattr(r_def, field), getattr(r_bfs, field), err_msg=field
        )


# -- windowed execution across the algebra ------------------------------------


@pytest.mark.parametrize("make_prog", [WccProgram, lambda: PageRankProgram(num_iters=13)])
def test_run_window_chaining_matches_run(pg_unweighted, make_prog):
    """Chained run_window must reproduce run() for monotone source-free AND
    stationary programs (the budget must survive window boundaries)."""
    prog = make_prog()
    eng = get_engine(pg_unweighted, program=prog, config=EngineConfig(m_max=64))
    full = eng.run([0])
    for k in (1, 3, 7):
        state = eng.init_state([0])
        chunks = []
        for _ in range(64):
            w = eng.run_window(state, k)
            state = w.state
            chunks.append(w)
            if w.done.all():
                break
        assert chunks[-1].done.all()
        we = np.concatenate([c.edges_examined for c in chunks], axis=1)
        m = we.shape[1]
        np.testing.assert_array_equal(we, full.edges_examined[:, :m])
        np.testing.assert_array_equal(np.asarray(state.dist), full.dist)
        np.testing.assert_array_equal(
            np.asarray(state.n_supersteps), full.n_supersteps
        )


# -- the elastic executor across stationary / non-stationary workloads --------


def test_executor_runs_wcc(pg_two_components):
    pg = pg_two_components
    prog = WccProgram()
    _, traces = run_program(pg, prog, [0], max_supersteps=256)
    plan = ffd_placement(TimeFunction.from_trace(traces[0]))
    rep = ElasticBSPExecutor(
        pg, program=prog, config=EngineConfig(window=4)
    ).run(0, plan, max_supersteps=256)
    np.testing.assert_array_equal(rep.dist, reference_wcc(pg).astype(np.int32))
    # WCC starts everywhere: superstep 0 must have every partition active
    assert traces[0].active[0].all()


def test_executor_runs_pagerank_and_profile_is_stationary(pg_unweighted):
    """PageRank under the executor: correct ranks, and the designed contrast
    case -- every partition active at every superstep, so elasticity has
    nothing to harvest until the budget ends."""
    pg = pg_unweighted
    prog = PageRankProgram(num_iters=11)
    _, traces = run_program(pg, prog, [0], max_supersteps=64)
    trace = traces[0]
    assert trace.n_supersteps == 11
    assert trace.active.all()  # stationary: flat activity profile
    plan = ffd_placement(TimeFunction.from_trace(trace))
    rep = ElasticBSPExecutor(
        pg, program=prog, config=EngineConfig(window=4)
    ).run(
        0, plan, strategy_fn=ffd_placement, replan=True, max_supersteps=64,
    )
    np.testing.assert_allclose(
        rep.dist, reference_pagerank(pg, 0.85, 11), rtol=1e-5, atol=1e-9
    )
    assert rep.n_supersteps == 11
    # the executed tau is flat-active too (what the replanner observed)
    assert (rep.actual_tau.tau > 0).all()


def test_initial_active_parts(pg_unweighted):
    pg = pg_unweighted
    one_hot = SsspProgram().initial_active_parts(pg, [5])
    expect = np.zeros(pg.n_parts, dtype=bool)
    expect[pg.part_of_vertex[5]] = True
    np.testing.assert_array_equal(one_hot, expect)
    for prog in (WccProgram(), PageRankProgram(num_iters=2)):
        assert prog.initial_active_parts(pg, [5]).all()


# -- plane plumbing, spec validation, registry --------------------------------


def test_pagerank_edge_plane_is_inverse_out_degree(pg_unweighted):
    pg = pg_unweighted
    plane = PageRankProgram(num_iters=2).edge_plane(pg)
    deg = pg.graph.out_degree
    np.testing.assert_allclose(
        plane, 1.0 / np.maximum(deg, 1)[pg.graph.src], rtol=1e-6
    )


def test_plane_arrays_cached_per_key(pg_weighted):
    a = plane_arrays(pg_weighted, BfsProgram())
    b = plane_arrays(pg_weighted, BfsProgram())
    assert a[0] is b[0] and a[1] is b[1]  # cached on the graph by plane_key
    lw, rw = plane_arrays(pg_weighted, SsspProgram())
    assert not np.array_equal(np.asarray(a[0]), np.asarray(lw))  # unit != graph


def test_validate_program_rejects_bad_specs():
    class MonotoneSum(VertexProgram):
        name = "bad-monotone-sum"
        reduce = "sum"
        stationary = False

    with pytest.raises(NotImplementedError, match="stationary"):
        validate_program(MonotoneSum())

    class NoBudget(VertexProgram):
        name = "bad-no-budget"
        reduce = "sum"
        stationary = True

    with pytest.raises(ValueError, match="superstep_budget"):
        validate_program(NoBudget())

    with pytest.raises(ValueError, match="damping"):
        PageRankProgram(damping=1.5)


def test_builtin_registry_and_engine_cache(pg_unweighted):
    assert set(BUILTIN_PROGRAMS) == {"bfs", "sssp", "wcc", "pagerank"}
    # equal program keys share one cached engine; distinct keys do not
    e1 = get_engine(
        pg_unweighted, program=SsspProgram(), config=EngineConfig(m_max=64),
    )
    e2 = get_engine(
        pg_unweighted, program=SsspProgram(), config=EngineConfig(m_max=64),
    )
    # the default program is SsspProgram
    e3 = get_engine(pg_unweighted, config=EngineConfig(m_max=64))
    assert e1 is e2 is e3
    assert get_engine(
        pg_unweighted, program=BfsProgram(), config=EngineConfig(m_max=64),
    ) is not e1
    assert get_engine(
        pg_unweighted,
        program=PageRankProgram(num_iters=3),
        config=EngineConfig(m_max=64),
    ) is not get_engine(
        pg_unweighted,
        program=PageRankProgram(num_iters=4),
        config=EngineConfig(m_max=64),
    )


def test_replan_config_follows_program_shape():
    assert ReplanConfig.for_program(SsspProgram()) == ReplanConfig()
    cfg = ReplanConfig.for_program(PageRankProgram(num_iters=2))
    assert cfg.decay_default == 1.0  # stationary: no spurious decay


# -- seeded deterministic edge weights (generators satellite) -----------------


def test_weighted_is_seeded_deterministic_symmetric():
    g = erdos_renyi_graph(200, 4.0, seed=9)
    w1 = weighted(g, seed=1)
    w1b = weighted(g, seed=1)
    w2 = weighted(g, seed=2)
    np.testing.assert_array_equal(w1.weights, w1b.weights)  # deterministic
    assert not np.array_equal(w1.weights, w2.weights)  # seed matters
    assert (w1.weights > 0).all() and (w1.weights >= 1.0).all()
    # symmetric: (u, v) and (v, u) carry the same weight
    wmap = {}
    for s, d, w in zip(w1.src, w1.dst, w1.weights):
        key = (min(s, d), max(s, d))
        assert wmap.setdefault(key, w) == w
    with pytest.raises(ValueError, match="positive"):
        weighted(g, low=0.0)
