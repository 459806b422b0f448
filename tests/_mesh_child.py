"""Multi-device equivalence child (run by test_mesh_traversal via the
``mesh_subprocess`` fixture with XLA_FLAGS forcing 8 host devices).

Asserts, under real 8-device execution:
  * dynamic re-layout equivalence: runs with forced mid-traversal
    ``device_of_part`` swaps (ragged P=5, D in {2, 8}) keep counters
    bit-identical to the static-layout run for BFS *and* PageRank shapes,
    with state bit-identical for the monotone program and rounding-equal for
    the stationary one (float sums reassociate across layouts, same
    convention as the dense-vs-mesh checks), and the executor under
    ``EngineConfig(relayout=True)`` reproduces the static run's dist / executed tau /
    billed economics exactly while residency tracks the planned devices,
  * engine equivalence: the engine under
    ``EngineConfig(mesh=partition_mesh(D))`` produces bit-identical dist and ``[S, m_max, P]`` counters vs the dense engine
    for D in {1, 2, 8}, on an R-MAT and an Erdos-Renyi graph -- including
    the ragged case (P=5 partitions, not divisible by any D tested),
  * cross-program equivalence on the ragged P=5 graph: weighted SSSP and
    WCC through the VertexProgram API are bit-identical dense-vs-mesh for
    D in {2, 8} (state AND counters) and match their numpy references;
    stationary PageRank keeps exact counters with state equal to rounding
    (float sums reassociate across shards) and matches its reference,
  * counters from per-vertex degrees: on a graph with duplicate arcs and
    arc-less vertices, every builtin program's mesh counters match the dense
    engine bit for bit across forced relayouts, unmirrored and mirrored
    (monotone state bit-identical, PageRank's to rounding),
  * per-destination aggregation puts fewer messages on the wire than the
    raw active-remote-edge count -- for every program,
  * windowed chaining on the mesh engine (k in {1, 8}) reproduces the
    single-launch results,
  * executor equivalence: ``ElasticBSPExecutor`` on a mesh yields
    bit-identical dist, executed tau, and ``migration_secs`` for
    D in {1, 2, 8} and window k in {1, 8} (the billed cloud migration must
    not depend on how many local devices stand in for the VMs), while the
    *physical* ledger (``device_moves``) only counts real device crossings:
    0 on one device, > 0 on 8 when the plan migrates.

Exit 0 == all assertions passed; all output is diagnostics for failures.
"""

import numpy as np

import jax

assert len(jax.devices()) == 8, f"expected 8 forced devices, got {jax.devices()}"

from repro.core import TimeFunction, ffd_placement
from repro.core.elastic import ElasticBSPExecutor
from repro.dist.sharding import partition_mesh
from repro.graph.bsp import run_sssp
from repro.graph.config import EngineConfig
from repro.graph.generators import erdos_renyi_graph, rmat_graph, weighted
from repro.graph.partition import bfs_grow_partition
from repro.graph.program import PageRankProgram, SsspProgram, WccProgram
from repro.graph.structs import PartitionedGraph
from repro.graph.traversal import (
    TraversalEngine,
    get_engine,
    reference_pagerank,
    reference_sssp,
    reference_wcc,
)

M_MAX = 64
MESH_SIZES = (1, 2, 8)
WINDOWS = (1, 8)

graphs = {
    "rmat": bfs_grow_partition(rmat_graph(9, 6, seed=3), 6, seed=1),
    "erdos_ragged_p5": bfs_grow_partition(
        erdos_renyi_graph(400, 4.0, seed=7), 5, seed=2
    ),
}

# -- engine equivalence ------------------------------------------------------
for name, pg in graphs.items():
    sources = [0, 17, pg.graph.n_vertices - 1]
    dense = get_engine(pg, config=EngineConfig(m_max=M_MAX)).run(sources)
    for d_n in MESH_SIZES:
        eng = get_engine(
            pg, config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
        )
        res = eng.run(sources)
        for field in (
            "dist",
            "n_supersteps",
            "edges_examined",
            "verts_processed",
            "msgs_sent",
            "inner_iters",
        ):
            np.testing.assert_array_equal(
                getattr(res, field),
                getattr(dense, field),
                err_msg=f"{name} D={d_n} field={field}",
            )
        wire = int(res.wire_msgs.sum())
        pre_agg = int(res.msgs_sent.sum())
        if d_n == 1:
            assert wire == 0, f"{name}: dense fallback put {wire} on a wire"
        else:
            assert 0 < wire < pre_agg, (
                f"{name} D={d_n}: aggregation must shrink the wire "
                f"(wire={wire}, raw active remote edges={pre_agg})"
            )
        print(f"engine {name} D={d_n}: bit-identical, wire={wire}/{pre_agg}")

# -- cross-program equivalence on the ragged P=5 graph -----------------------
COUNTERS = (
    "n_supersteps", "edges_examined", "verts_processed", "msgs_sent",
    "inner_iters",
)
pg5 = graphs["erdos_ragged_p5"]
pg5w = PartitionedGraph(  # weighted twin, same ragged partition map
    weighted(pg5.graph, seed=4), pg5.n_parts, pg5.part_of_vertex
)
n5 = pg5.graph.n_vertices


def assert_state(actual, expect, exact, err_msg=""):
    if exact:
        np.testing.assert_array_equal(actual, expect, err_msg=err_msg)
    else:  # float sums reassociate across shards: equal to rounding only
        np.testing.assert_allclose(
            actual, expect, rtol=1e-5, atol=1e-9, err_msg=err_msg
        )


def check_program(name, prog, pgx, srcs, refs, *, state_exact, mesh_sizes):
    """Dense run vs numpy reference, then dense-vs-mesh equivalence: integer
    counters always bit-identical; state bit-identical for min-programs and
    rounding-tolerant for stationary sums.  Returns the dense result."""
    dense = get_engine(pgx, program=prog, config=EngineConfig(m_max=M_MAX)).run(srcs)
    for i, ref in enumerate(refs):
        assert_state(
            dense.dist[i], ref, state_exact and ref.dtype == dense.dist.dtype,
            err_msg=f"{name} dense vs reference, source row {i}",
        )
    for d_n in mesh_sizes:
        res = get_engine(
            pgx,
            program=prog,
            config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
        ).run(srcs)
        for field in COUNTERS:
            np.testing.assert_array_equal(
                getattr(res, field), getattr(dense, field),
                err_msg=f"{name} D={d_n} field={field}",
            )
        assert_state(res.dist, dense.dist, state_exact, f"{name} D={d_n} dist")
        wire, pre = int(res.wire_msgs.sum()), int(res.msgs_sent.sum())
        assert 0 < wire < pre, f"{name} D={d_n}: wire={wire} pre={pre}"
    print(f"program {name}: dense==mesh for D in {mesh_sizes}")
    return dense


srcs = [0, 17, n5 - 1]
check_program(
    # float64 reference stays float64: the f32 engine matches it to rounding
    # (the dtype check in assert_state routes to allclose), while the
    # dense-vs-mesh comparison below stays bit-exact
    "sssp (weighted, ragged P=5)", SsspProgram(), pg5w, srcs,
    [reference_sssp(pg5w, s) for s in srcs],
    state_exact=True, mesh_sizes=(2, 8),
)
check_program(
    "wcc (ragged P=5)", WccProgram(), pg5, [0],
    [reference_wcc(pg5).astype(np.int32)],
    state_exact=True, mesh_sizes=(2, 8),
)
pr = PageRankProgram(num_iters=12)
check_program(
    "pagerank (stationary)", pr, pg5, [0],
    [reference_pagerank(pg5, 0.85, 12)],
    state_exact=False, mesh_sizes=(8,),
)

# stationary windowed chaining on the mesh: the iteration budget and the
# carried nst must survive window boundaries across 8 real devices
eng = get_engine(
    pg5, program=pr, config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(8)),
)
full = eng.run([0])
for k in (3, 8):
    state = eng.init_state([0])
    chunks = []
    for _ in range(M_MAX):
        w = eng.run_window(state, k)
        state = w.state
        chunks.append(w)
        if w.done.all():
            break
    assert chunks[-1].done.all()
    we = np.concatenate([c.edges_examined for c in chunks], axis=1)
    m = we.shape[1]
    np.testing.assert_array_equal(we, full.edges_examined[:, :m])
    np.testing.assert_array_equal(
        np.asarray(state.n_supersteps), full.n_supersteps
    )
    np.testing.assert_allclose(
        eng.gather_global(np.asarray(state.dist)), full.dist,
        rtol=1e-5, atol=1e-9,
    )
print("program pagerank: mesh windowed chaining k in (3, 8) keeps the budget")

# -- windowed chaining on the mesh engine ------------------------------------
pg = graphs["rmat"]
sources = [0, 17, pg.graph.n_vertices - 1]
dense = get_engine(pg, config=EngineConfig(m_max=M_MAX)).run(sources)
eng = get_engine(pg, config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(8)))
for k in WINDOWS:
    state = eng.init_state(sources)
    chunks = []
    for _ in range(M_MAX):
        w = eng.run_window(state, k)
        state = w.state
        chunks.append(w)
        if w.done.all():
            break
    assert chunks[-1].done.all()
    we = np.concatenate([c.edges_examined for c in chunks], axis=1)
    wv = np.concatenate([c.verts_processed for c in chunks], axis=1)
    m = we.shape[1]
    np.testing.assert_array_equal(we, dense.edges_examined[:, :m])
    np.testing.assert_array_equal(wv, dense.verts_processed[:, :m])
    np.testing.assert_array_equal(
        eng.gather_global(np.asarray(state.dist)), dense.dist
    )
    np.testing.assert_array_equal(
        np.asarray(state.n_supersteps), dense.n_supersteps
    )
    print(f"mesh windowed chaining k={k}: OK")

# -- dynamic re-layout: forced mid-traversal device_of_part swaps ------------
# the compute layout changes every window; dist/counters must not notice.


def run_with_swaps(
    pgx, prog, srcs, d_n, swap_seq, k=2, backend="xla", mirror_degree=None
):
    """Windowed run forcing a different device_of_part each window."""
    eng = TraversalEngine(
        pgx,
        program=prog,
        config=EngineConfig(
            m_max=M_MAX, mesh=partition_mesh(d_n), backend=backend,
            mirror_degree=mirror_degree,
        ),
    )
    state = eng.init_state(srcs)
    chunks = []
    for i in range(M_MAX):
        w = eng.run_window(state, k, device_of_part=swap_seq[i % len(swap_seq)])
        state = w.state
        chunks.append(w)
        if w.done.all():
            break
    assert chunks[-1].done.all()
    we = np.concatenate([c.edges_examined for c in chunks], axis=1)
    wv = np.concatenate([c.verts_processed for c in chunks], axis=1)
    ms = np.concatenate([c.msgs_sent for c in chunks], axis=1)
    return eng, state, we, wv, ms


rng = np.random.default_rng(11)
for prog_name, prog, state_exact in (
    ("bfs-shape", SsspProgram(), True),
    ("pagerank-shape", PageRankProgram(num_iters=12), False),
):
    sources = [0] if prog.stationary else srcs
    for d_n in (2, 8):
        base = get_engine(
            pg5,
            program=prog,
            config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
        ).run(sources)
        swap_seq = [
            np.arange(5, dtype=np.int32) % d_n,
            (np.arange(5, dtype=np.int32)[::-1] % d_n).copy(),
            rng.integers(0, d_n, size=5).astype(np.int32),
        ]
        eng, state, we, wv, ms = run_with_swaps(
            pg5, prog, sources, d_n, swap_seq
        )
        m = we.shape[1]
        np.testing.assert_array_equal(we, base.edges_examined[:, :m])
        np.testing.assert_array_equal(wv, base.verts_processed[:, :m])
        np.testing.assert_array_equal(ms, base.msgs_sent[:, :m])
        np.testing.assert_array_equal(
            np.asarray(state.n_supersteps), base.n_supersteps
        )
        assert_state(
            eng.gather_global(np.asarray(state.dist)), base.dist, state_exact,
            err_msg=f"relayout {prog_name} D={d_n} dist",
        )
        print(f"relayout {prog_name} D={d_n}: swapped layouts, same results")

# -- kernel backend parity on the mesh path ----------------------------------
# pallas-interpret runs the block-skipping relax kernels inside the
# shard_map body (local reduction + pre-all-to-all wire aggregation);
# counters/collectives stay on XLA so they must stay bit-identical, state
# is bit-identical for min-programs and rounding-equal for the sum path.
from repro.graph.program import BfsProgram

BACKEND_COUNTERS = COUNTERS + ("wire_msgs",)
for prog_name, prog_ctor, pgx, b_srcs, state_exact in (
    ("bfs", BfsProgram, pg5, srcs, True),
    ("sssp", SsspProgram, pg5w, srcs, True),
    ("wcc", WccProgram, pg5, [0], True),
    ("pagerank", lambda: PageRankProgram(num_iters=12), pg5, [0], False),
):
    for d_n in (2, 8):
        rx = get_engine(
            pgx,
            program=prog_ctor(),
            config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n), backend="xla"),
        ).run(b_srcs)
        rk = get_engine(
            pgx,
            program=prog_ctor(),
            config=EngineConfig(
                m_max=M_MAX, mesh=partition_mesh(d_n), backend="pallas-interpret",
            ),
        ).run(b_srcs)
        for field in BACKEND_COUNTERS:
            np.testing.assert_array_equal(
                getattr(rk, field), getattr(rx, field),
                err_msg=f"backend {prog_name} D={d_n} field={field}",
            )
        assert_state(
            rk.dist, rx.dist, state_exact,
            err_msg=f"backend {prog_name} D={d_n} dist",
        )
    print(f"backend parity {prog_name}: pallas-interpret==xla for D in (2, 8)")

# mid-traversal relayout swaps under the kernel backend: the carried block
# maps (incrementally rebuilt with the layout) must keep results identical
for d_n in (2, 8):
    base = get_engine(
        pg5w,
        program=SsspProgram(),
        config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
    ).run(srcs)
    swap_seq = [
        np.arange(5, dtype=np.int32) % d_n,
        (np.arange(5, dtype=np.int32)[::-1] % d_n).copy(),
    ]
    eng, state, we, wv, ms = run_with_swaps(
        pg5w, SsspProgram(), srcs, d_n, swap_seq, backend="pallas-interpret"
    )
    m = we.shape[1]
    np.testing.assert_array_equal(we, base.edges_examined[:, :m])
    np.testing.assert_array_equal(wv, base.verts_processed[:, :m])
    np.testing.assert_array_equal(ms, base.msgs_sent[:, :m])
    np.testing.assert_array_equal(
        eng.gather_global(np.asarray(state.dist)), base.dist
    )
    print(f"backend relayout D={d_n}: kernel path swaps layouts, same results")

# degenerate mesh path: two disconnected halves, each on its own device ->
# zero real remote edges (the remote shard is pure padding); both backends
# must agree and put nothing on the wire
half = 40
src_a = np.arange(half - 1, dtype=np.int32)
two_cliques = np.concatenate([src_a, src_a + half])
dst_a = np.arange(1, half, dtype=np.int32)
two_cliques_dst = np.concatenate([dst_a, dst_a + half])
from repro.graph.structs import Graph

g_split = Graph(2 * half, two_cliques, two_cliques_dst, None)
pg_split = PartitionedGraph(
    g_split, 2, (np.arange(2 * half) >= half).astype(np.int32)
)
for backend in ("xla", "pallas-interpret"):
    r = get_engine(
        pg_split,
        config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(2), backend=backend),
    ).run([0, half])
    assert int(r.wire_msgs.sum()) == 0, (backend, int(r.wire_msgs.sum()))
    if backend == "xla":
        r_ref = r
    else:
        np.testing.assert_array_equal(r.dist, r_ref.dist)
        np.testing.assert_array_equal(r.edges_examined, r_ref.edges_examined)
print("backend degenerate: no-remote-edge mesh agrees across backends")

# -- hub mirroring: mirrored engine parity ------------------------------------
# remote edges into high-in-degree vertices are rewritten onto local mirror
# slots and synced through a second all_to_all; results must be bit-identical
# (state + every counter) for min-programs, counters-exact/state-allclose for
# PageRank, with strictly fewer wire messages for the monotone programs
# (cache suppression) and unchanged wire billing for the stationary one.
MIRROR_DEGREE = 3  # pg5 at this threshold: 110 hubs / 422 of 698 remote edges
for prog_name, prog_ctor, pgx, m_srcs, state_exact in (
    ("bfs", BfsProgram, pg5, srcs, True),
    ("sssp", SsspProgram, pg5w, srcs, True),
    ("wcc", WccProgram, pg5, [0], True),
    ("pagerank", lambda: PageRankProgram(num_iters=12), pg5, [0], False),
):
    for d_n in (2, 8):
        r0 = get_engine(
            pgx,
            program=prog_ctor(),
            config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
        ).run(m_srcs)
        r1 = get_engine(
            pgx,
            program=prog_ctor(),
            config=EngineConfig(
                m_max=M_MAX, mesh=partition_mesh(d_n), mirror_degree=MIRROR_DEGREE,
            ),
        ).run(m_srcs)
        for field in COUNTERS:
            np.testing.assert_array_equal(
                getattr(r1, field), getattr(r0, field),
                err_msg=f"mirror {prog_name} D={d_n} field={field}",
            )
        assert_state(
            r1.dist, r0.dist, state_exact,
            err_msg=f"mirror {prog_name} D={d_n} dist",
        )
        w0, w1 = int(r0.wire_msgs.sum()), int(r1.wire_msgs.sum())
        if prog_name == "pagerank":
            assert w1 == w0, f"mirror pagerank D={d_n}: {w1} != {w0}"
        else:
            assert 0 < w1 < w0, (
                f"mirror {prog_name} D={d_n}: mirroring must shrink the "
                f"wire ({w1} vs {w0})"
            )
    print(f"mirror parity {prog_name}: mirrored==unmirrored for D in (2, 8)")

# mid-traversal relayout swaps UNDER mirroring: the mirror plane is carried
# through the incremental layout rebuild; swapping every window must keep
# results identical to the static unmirrored run (hub set is
# partition-determined, so it survives device-map changes)
for d_n in (2, 8):
    base = get_engine(
        pg5w,
        program=SsspProgram(),
        config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
    ).run(srcs)
    swap_seq = [
        np.arange(5, dtype=np.int32) % d_n,
        (np.arange(5, dtype=np.int32)[::-1] % d_n).copy(),
    ]
    eng, state, we, wv, ms = run_with_swaps(
        pg5w, SsspProgram(), srcs, d_n, swap_seq,
        mirror_degree=MIRROR_DEGREE,
    )
    m = we.shape[1]
    np.testing.assert_array_equal(we, base.edges_examined[:, :m])
    np.testing.assert_array_equal(wv, base.verts_processed[:, :m])
    np.testing.assert_array_equal(ms, base.msgs_sent[:, :m])
    np.testing.assert_array_equal(
        eng.gather_global(np.asarray(state.dist)), base.dist
    )
    print(f"mirror relayout D={d_n}: swapped mirrored layouts, same results")

# kernel backend under mirroring: the mirror combine routes through the
# same block-map Pallas kernels; counters and state must match xla exactly
for d_n in (2, 8):
    rx = get_engine(
        pg5w,
        program=SsspProgram(),
        config=EngineConfig(
            m_max=M_MAX, mesh=partition_mesh(d_n), backend="xla",
            mirror_degree=MIRROR_DEGREE,
        ),
    ).run(srcs)
    rk = get_engine(
        pg5w,
        program=SsspProgram(),
        config=EngineConfig(
            m_max=M_MAX, mesh=partition_mesh(d_n), backend="pallas-interpret",
            mirror_degree=MIRROR_DEGREE,
        ),
    ).run(srcs)
    for field in BACKEND_COUNTERS:
        np.testing.assert_array_equal(
            getattr(rk, field), getattr(rx, field),
            err_msg=f"mirror backend D={d_n} field={field}",
        )
    np.testing.assert_array_equal(rk.dist, rx.dist)
print("mirror backend parity: pallas-interpret==xla for D in (2, 8)")

# -- counters from per-vertex degrees: duplicate arcs, arc-less vertices ------
# the mesh window counts work from per-vertex out-degrees; on a graph with
# repeated arcs (each counts) and vertices with no arc at all, every builtin
# program's counters must match the dense engine bit for bit across forced
# relayouts, unmirrored and mirrored, with monotone state bit-identical
rng_dup = np.random.default_rng(4)
dup_src = rng_dup.integers(0, 140, 700)
dup_dst = (dup_src + rng_dup.integers(1, 140, 700)) % 140
again = rng_dup.choice(700, 120, replace=False)
dup_src = np.concatenate([dup_src, dup_src[again]]).astype(np.int32)
dup_dst = np.concatenate([dup_dst, dup_dst[again]]).astype(np.int32)
dup_w = rng_dup.uniform(1.0, 4.0, dup_src.shape[0]).astype(np.float32)
pg_dup = bfs_grow_partition(Graph(160, dup_src, dup_dst, dup_w), 5, seed=2)
for prog_name, prog_ctor, d_srcs, state_exact in (
    ("bfs", BfsProgram, [0, 77, 150], True),
    ("sssp", SsspProgram, [0, 77, 150], True),
    ("wcc", WccProgram, [0], True),
    ("pagerank", lambda: PageRankProgram(num_iters=6), [0], False),
):
    dense = get_engine(
        pg_dup, program=prog_ctor(), config=EngineConfig(m_max=M_MAX)
    ).run(d_srcs)
    for d_n in (2, 8):
        swap_seq = [
            np.arange(5, dtype=np.int32) % d_n,
            (np.arange(5, dtype=np.int32)[::-1] % d_n).copy(),
        ]
        for mirror_degree in (None, MIRROR_DEGREE):
            eng, state, we, wv, ms = run_with_swaps(
                pg_dup, prog_ctor(), d_srcs, d_n, swap_seq,
                mirror_degree=mirror_degree,
            )
            assert (eng._mesh_prog.layout.m_pad > 0) == bool(mirror_degree)
            m = we.shape[1]
            tag = f"degrees {prog_name} D={d_n} mirror={mirror_degree}"
            np.testing.assert_array_equal(
                we, dense.edges_examined[:, :m], err_msg=tag
            )
            np.testing.assert_array_equal(
                wv, dense.verts_processed[:, :m], err_msg=tag
            )
            np.testing.assert_array_equal(
                ms, dense.msgs_sent[:, :m], err_msg=tag
            )
            assert not dense.edges_examined[:, m:].any(), tag
            np.testing.assert_array_equal(
                np.asarray(state.n_supersteps), dense.n_supersteps, err_msg=tag
            )
            assert_state(
                eng.gather_global(np.asarray(state.dist)), dense.dist,
                state_exact, err_msg=tag,
            )
    print(f"degree counters {prog_name}: mesh==dense across relayouts")

# -- executor relayout="auto": same results, skips recorded -------------------
# the cost-aware policy may veto swaps but never changes results or the
# billed economics; relayout=True keeps its unconditional behavior.
_, trace5 = run_sssp(graphs["rmat"], 0)
plan5 = ffd_placement(TimeFunction.from_trace(trace5))
mesh8 = partition_mesh(8)
cfg8 = EngineConfig(mesh=mesh8, window=1)
rep_s = ElasticBSPExecutor(graphs["rmat"], config=cfg8).run(0, plan5)
rep_t = ElasticBSPExecutor(
    graphs["rmat"], config=cfg8.replace(relayout=True)
).run(0, plan5)
rep_a = ElasticBSPExecutor(
    graphs["rmat"], config=cfg8.replace(relayout="auto")
).run(0, plan5)
assert rep_t.relayouts_skipped == 0, "relayout=True must never skip"
np.testing.assert_array_equal(rep_a.dist, rep_s.dist)
np.testing.assert_array_equal(rep_a.actual_tau.tau, rep_s.actual_tau.tau)
assert rep_a.cost.migration_secs == rep_s.cost.migration_secs
assert rep_a.cost.cost_quanta == rep_s.cost.cost_quanta
assert rep_a.relayouts <= rep_t.relayouts

# force the payback bar impossibly high: every proposed swap is vetoed,
# the skip counter records each veto, and results are still identical
ex_never = ElasticBSPExecutor(
    graphs["rmat"], config=cfg8.replace(relayout="auto")
)
ex_never.AUTO_RELAYOUT_MIN_STEPS = 10**9
rep_n = ex_never.run(0, plan5)
assert rep_n.relayouts == 0, "an infinite payback bar must veto every swap"
if rep_t.relayouts:
    assert rep_n.relayouts_skipped > 0, (
        "relayout=True swapped but the always-veto auto run recorded no skips"
    )
assert rep_n.device_move_bytes <= rep_t.device_move_bytes
np.testing.assert_array_equal(rep_n.dist, rep_s.dist)
print(
    f"executor relayout=auto: {rep_a.relayouts} committed, "
    f"{rep_a.relayouts_skipped} skipped (always-veto run: "
    f"{rep_n.relayouts_skipped} skips), results identical"
)

# -- executor dynamic re-layout: identical economics, planned residency ------
for name, pg_x in graphs.items():
    _, trace = run_sssp(pg_x, 0)
    plan = ffd_placement(TimeFunction.from_trace(trace))
    swapped_any = 0
    for d_n in (2, 8):
        mesh = partition_mesh(d_n)
        cfg = EngineConfig(mesh=mesh, window=1)
        rep_s = ElasticBSPExecutor(pg_x, config=cfg).run(0, plan)
        rep_d = ElasticBSPExecutor(
            pg_x, config=cfg.replace(relayout=True)
        ).run(0, plan)
        np.testing.assert_array_equal(rep_d.dist, rep_s.dist)
        np.testing.assert_array_equal(rep_d.actual_tau.tau, rep_s.actual_tau.tau)
        assert rep_d.cost.migration_secs == rep_s.cost.migration_secs
        assert rep_d.cost.cost_quanta == rep_s.cost.cost_quanta
        assert rep_d.cost.makespan == rep_s.cost.makespan
        assert rep_d.n_migrations == rep_s.n_migrations
        # every placed partition computes on its planned device, every window
        for w in range(min(rep_d.residency.shape[0], plan.vm_of.shape[0])):
            row = plan.vm_of[w]
            placed = row >= 0
            np.testing.assert_array_equal(
                rep_d.residency[w][placed],
                row[placed] % d_n,
                err_msg=f"{name} D={d_n} window {w}: residency off-plan",
            )
        swapped_any += rep_d.relayouts
    assert swapped_any > 0, f"{name}: relayout executor never swapped a layout"
    print(f"executor relayout {name}: billing identical, residency on-plan")

# -- executor equivalence across mesh sizes ----------------------------------
for name, pg in graphs.items():
    _, trace = run_sssp(pg, 0)
    plan = ffd_placement(TimeFunction.from_trace(trace))
    base = {}
    for k in WINDOWS:
        for d_n in MESH_SIZES:
            ex = ElasticBSPExecutor(
                pg, config=EngineConfig(mesh=partition_mesh(d_n), window=k),
            )
            rep = ex.run(0, plan)
            if k not in base:
                base[k] = rep
            ref = base[k]
            np.testing.assert_array_equal(rep.dist, ref.dist)
            np.testing.assert_array_equal(
                rep.actual_tau.tau, ref.actual_tau.tau
            )
            assert rep.n_migrations == ref.n_migrations
            assert rep.migration_bytes == ref.migration_bytes
            assert rep.cost.migration_secs == ref.cost.migration_secs, (
                f"{name} k={k} D={d_n}: billed migration depends on the "
                f"device count ({rep.cost.migration_secs} vs "
                f"{ref.cost.migration_secs})"
            )
            if d_n == 1:
                assert rep.device_moves == 0, "one device cannot cross"
            elif rep.n_migrations > 0 and d_n == 8:
                assert rep.device_moves > 0, (
                    f"{name} k={k}: plan migrates but no shard crossed "
                    f"the 8-device mesh"
                )
                assert rep.device_move_bytes <= rep.migration_bytes
            assert rep.residency is not None and rep.residency.shape[1] == pg.n_parts
        print(
            f"executor {name} k={k}: dist/tau/migration_secs identical over "
            f"D={MESH_SIZES}, physical moves D=8: {base[k].n_migrations and 'yes' or 'n/a'}"
        )

# -- streaming delta merges: mid-traversal state carried exactly -------------
# between windows, merge an EdgeDeltaBuffer through GraphSession.apply_deltas
# at D in {2, 8} on the ragged P=5 weighted graph: the carried state must be
# bit-identical across the merge (gathered dist + superstep counters
# unchanged), and the continued traversal must land exactly on the mutated
# graph's fixpoint (the inserted 0.5-weight shortcuts change it, so the
# reactivation path is what makes this pass).
from repro.graph import open_session
from repro.graph.deltas import EdgeDeltaBuffer, apply_delta_buffer

rng_d = np.random.default_rng(21)
buf5 = EdgeDeltaBuffer()
for v in rng_d.choice(n5, size=12, replace=False):
    u = int((int(v) + n5 // 2) % n5)
    buf5.insert(int(v), u, 0.5)
    buf5.insert(u, int(v), 0.5)

new_pg5w = apply_delta_buffer(pg5w, buf5)
for d_n in (2, 8):
    cfg = EngineConfig(mesh=partition_mesh(d_n), m_max=M_MAX)
    sess = open_session(pg5w, cfg)
    state = sess.init_state(srcs)
    w = sess.run_window(state, 3)
    state = w.state
    pre_dist = sess.gather_global(state.dist)
    pre_steps = np.asarray(state.n_supersteps).copy()

    state = sess.apply_deltas(buf5, state=state)
    assert sess.pg is not pg5w and sess.pg.graph.n_edges == new_pg5w.graph.n_edges
    np.testing.assert_array_equal(
        sess.gather_global(state.dist), pre_dist,
        err_msg=f"delta merge D={d_n}: carried dist not bit-identical",
    )
    np.testing.assert_array_equal(
        np.asarray(state.n_supersteps), pre_steps,
        err_msg=f"delta merge D={d_n}: superstep counters changed",
    )

    for _ in range(M_MAX):
        w = sess.run_window(state, 3)
        state = w.state
        if w.done.all():
            break
    assert w.done.all(), f"delta merge D={d_n}: continued run never converged"
    fresh = sess.run(sources=srcs)  # fresh fixpoint on the mutated graph
    np.testing.assert_array_equal(
        sess.gather_global(state.dist), fresh.dist,
        err_msg=f"delta merge D={d_n}: continued run != mutated fixpoint",
    )
    # the shortcuts must actually matter, or the reactivation is untested
    base5 = get_engine(
        pg5w, config=EngineConfig(m_max=M_MAX, mesh=partition_mesh(d_n)),
    ).run(srcs)
    assert not np.array_equal(np.asarray(fresh.dist), np.asarray(base5.dist)), (
        f"delta merge D={d_n}: inserted shortcuts changed nothing"
    )
    print(f"delta merge D={d_n}: carried state bit-identical, fixpoint exact")

print("ALL MESH CHECKS PASSED")
