"""Chip smoke: the traversal engine's main path, once, on TPU at Graph500 size.

Run from the root of a checkout on a machine with a TPU:

    python chip_smoke.py            # one chip: session, executor, service
    python chip_smoke.py --mesh 4   # four chips: the mesh engine only

The workload is Graph500's R-MAT at scale 20, edge factor 16 (a=0.57,
b=0.19, c=0.19), seed 42, symmetrized: 1,048,576 vertices and 32,205,848
arcs, BFS-grown into 8 partitions.  It is generated here, in this process,
and nothing is read from a cache.

One chip, all through ``open_session(pg, EngineConfig(backend="pallas"))``:

  * BFS from 8 sources as one batch;
  * SSSP from the same sources on ``weighted(...)`` of the graph;
  * WCC, and PageRank at its ``superstep_budget``;
  * BFS again with ``backend="xla"``: its counters must be bit-identical;
  * one ``ElasticBSPExecutor.run`` (window 8, replanning on), planned from
    another source's trace, whose final state must equal the session's;
  * one ``TraversalService.run`` over a Poisson trace of 32 queries at
    ``s_batch=8``, in which every query must complete.

``--mesh 4`` runs BFS and SSSP from the same 8 sources on the mesh engine
over ``partition_mesh(4)``, with one mid-run ``device_of_part`` swap, and
no other phase.

Every phase is checked against a host reference that shares no code with
the engine: ``scipy.sparse.csgraph`` for BFS, SSSP and WCC, a scipy sparse
power iteration for PageRank.  Min-programs must match exactly.  SSSP runs
in float32, so its exact check is that the result is the float32
Bellman-Ford fixpoint of the graph (``sssp_fixpoint_ok``), which is unique
for positive weights; it must also agree with scipy's float64 distances to
float32 rounding.

Each phase prints one JSON record: wall seconds up to the host having the
result, the compile seconds inside them (JAX's own compile events),
supersteps, ``correct``, the relax kernel's grid, and the device's
``peak_bytes_in_use``.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU the script exits
non-zero before it builds anything.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SCALE = 20
EDGE_FACTOR = 16
GRAPH_SEED = 42
N_PARTS = 8
PARTITION_SEED = 1
N_SOURCES = 8
SOURCE_SEED = 7
N_QUERIES = 32
QUERY_RATE = 64.0  # per simulated second: a burst that fills the batch
QUERY_SEED = 3
WINDOW = 8  # supersteps per launch: executor and service
MESH_WINDOW = 2  # short windows, so the relayout swap lands mid-run
PAGERANK_DAMPING = 0.85
PAGERANK_ITERS = 20
PAGERANK_RTOL = 1e-4  # float32 sums over up to ~10^5 in-edges
SSSP_RTOL = 1e-5  # float32 path sums against float64


# -- device ------------------------------------------------------------------


def tpu_devices(n_chips: int):
    """The TPU devices, or exit non-zero: a smoke that finds no chip fails."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} chips; JAX found {len(devices)}"
        )
    return devices


def peak_bytes_in_use(devices) -> int | None:
    """Largest ``peak_bytes_in_use`` over ``devices``, where reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
#: (end, seconds) of every compile event in this process; jax.monitoring
#: listeners are process-wide and cannot be scoped to a caller
_compile_events: list = []


def listen_for_compiles() -> None:
    """Record JAX's trace, lowering and compile events from now on."""
    import jax

    if not getattr(listen_for_compiles, "on", False):

        def on_event(name, secs, **_):
            if name in _COMPILE_EVENTS:
                _compile_events.append((time.perf_counter(), secs))

        jax.monitoring.register_event_duration_secs_listener(on_event)
        listen_for_compiles.on = True


def compile_seconds_since(t0: float) -> float:
    """Seconds spent compiling since ``t0``: the union of the compile events
    that ended after it (tracing nests, so the events overlap)."""
    spans = sorted((end - secs, end) for end, secs in _compile_events if end > t0)
    total, reach = 0.0, t0
    for start, end in spans:
        start = max(start, reach)
        if end > start:
            total += end - start
            reach = end
    return total


# -- workload and host references --------------------------------------------


@dataclasses.dataclass
class Workload:
    pg: object  # PartitionedGraph, unit weights
    wpg: object  # the same partition over weighted(graph)
    sources: np.ndarray  # [N_SOURCES] int64
    setup: dict


def build_workload(scale: int = SCALE) -> Workload:
    from repro.graph.generators import rmat_graph, weighted
    from repro.graph.partition import bfs_grow_partition, partitioned_edge_layout
    from repro.graph.structs import PartitionedGraph

    t0 = time.perf_counter()
    g = rmat_graph(scale, EDGE_FACTOR, seed=GRAPH_SEED)
    t1 = time.perf_counter()
    pg = bfs_grow_partition(g, N_PARTS, seed=PARTITION_SEED)
    t2 = time.perf_counter()
    layout = partitioned_edge_layout(pg)
    t3 = time.perf_counter()
    wpg = PartitionedGraph(weighted(g), N_PARTS, pg.part_of_vertex)
    rng = np.random.default_rng(SOURCE_SEED)
    sources = rng.choice(g.n_vertices, N_SOURCES, replace=False).astype(np.int64)
    setup = {
        "scale": scale,
        "edge_factor": EDGE_FACTOR,
        "n_vertices": g.n_vertices,
        "n_arcs": g.n_edges,
        "local_arcs": layout.local.n_edges,
        "remote_arcs": layout.remote.n_edges,
        "n_parts": N_PARTS,
        "generate_s": t1 - t0,
        "partition_s": t2 - t1,
        "layout_s": t3 - t2,
    }
    return Workload(pg, wpg, sources, setup)


def _adjacency(g, weights):
    from scipy import sparse

    n = g.n_vertices
    return sparse.csr_matrix(
        (np.asarray(weights, np.float64), (g.src, g.dst)), shape=(n, n)
    )


def scipy_bfs(g, sources) -> np.ndarray:
    """[S, n] hop counts (inf where unreachable)."""
    from scipy.sparse import csgraph

    a = _adjacency(g, np.ones(g.n_edges))
    return csgraph.shortest_path(a, method="D", unweighted=True, indices=sources)


def scipy_sssp(g, sources) -> np.ndarray:
    """[S, n] float64 shortest-path distances over ``g.edge_weights``."""
    from scipy.sparse import csgraph

    return csgraph.dijkstra(_adjacency(g, g.edge_weights), indices=sources)


def scipy_wcc(g) -> np.ndarray:
    """[n] smallest vertex id of each vertex's weakly connected component."""
    from scipy.sparse import csgraph

    n = g.n_vertices
    n_comp, comp = csgraph.connected_components(
        _adjacency(g, np.ones(g.n_edges)), directed=True, connection="weak"
    )
    first = np.full(n_comp, n, dtype=np.int64)
    np.minimum.at(first, comp, np.arange(n))
    return first[comp]


def scipy_pagerank(g, damping: float, num_iters: int) -> np.ndarray:
    """[n] float64 power iteration: (1 - d)/n + d * sum rank[u]/deg[u]."""
    from scipy import sparse

    n = g.n_vertices
    inv_deg = 1.0 / np.maximum(g.out_degree, 1).astype(np.float64)
    m = sparse.csr_matrix((inv_deg[g.src], (g.dst, g.src)), shape=(n, n))
    rank = np.full(n, 1.0 / n)
    for _ in range(num_iters):
        rank = (1.0 - damping) / n + damping * (m @ rank)
    return rank


def sssp_fixpoint_ok(g, sources, dist: np.ndarray) -> bool:
    """Exact check of float32 SSSP: ``dist`` is the fixpoint of one float32
    relaxation, ``dist == min(init, min_{u->v} dist[u] + w)``.

    With weights >= 1 that fixpoint is unique, and it is what float32
    Bellman-Ford converges to: every finite entry is the float32 sum along
    a path from the source, and no path's sum undercuts it.
    """
    order = np.argsort(g.dst, kind="stable")
    dst = g.dst[order]
    src = g.src[order]
    w = g.edge_weights[order].astype(np.float32)
    heads = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
    for row, s in zip(np.asarray(dist, np.float32), sources):
        best = np.full(g.n_vertices, np.inf, np.float32)
        best[dst[heads]] = np.minimum.reduceat(row[src] + w, heads)
        best[s] = min(best[s], np.float32(0))
        if not np.array_equal(row, best):
            return False
    return True


@dataclasses.dataclass
class References:
    bfs: np.ndarray  # [S, n] hop counts
    sssp: np.ndarray  # [S, n] float64
    wcc: np.ndarray  # [n] int64
    pagerank: np.ndarray  # [n] float64
    seconds: float


def build_references(wl: Workload, *, wcc_pagerank: bool = True) -> References:
    t0 = time.perf_counter()
    g, wg = wl.pg.graph, wl.wpg.graph
    bfs = scipy_bfs(g, wl.sources)
    sssp = scipy_sssp(wg, wl.sources)
    wcc = pagerank = None
    if wcc_pagerank:
        wcc = scipy_wcc(g)
        pagerank = scipy_pagerank(g, PAGERANK_DAMPING, PAGERANK_ITERS)
    return References(bfs, sssp, wcc, pagerank, time.perf_counter() - t0)


def sssp_correct(wg, sources, dist, ref) -> tuple[bool, float]:
    """(exact float32 fixpoint and within float32 rounding of scipy, max
    relative error against scipy)."""
    rel = np.abs(dist.astype(np.float64) - ref) / np.maximum(ref, 1.0)
    err = float(np.nanmax(rel))
    ok = (
        bool(np.array_equal(np.isinf(dist), np.isinf(ref)))
        and err <= SSSP_RTOL
        and sssp_fixpoint_ok(wg, sources, dist)
    )
    return ok, err


# -- kernel grid -------------------------------------------------------------


def dense_kernel_record(pg, s_batch: int, inner_iters, n_supersteps, stationary):
    """The relax kernel's grid on the dense engine's two planes, and the
    passes the run made: one local pass per closure iteration (the batch
    iterates while any source does), one remote pass per superstep."""
    from repro.graph.partition import partitioned_edge_layout
    from repro.kernels.bfs_relax.ops import relax_grid

    layout = partitioned_edge_layout(pg)
    n = pg.graph.n_vertices
    local = relax_grid(layout.local.dst, n, s_batch)
    remote = relax_grid(layout.remote.dst, n, s_batch)
    m = int(np.max(n_supersteps))
    local_passes = m if stationary else int(
        np.asarray(inner_iters)[:, :m].max(axis=0).sum()
    )
    return {
        "local": local,
        "remote": remote,
        "local_passes": local_passes,
        "remote_passes": m,
        "grid_steps": local_passes * local["grid_steps"]
        + m * remote["grid_steps"],
    }


# -- phases ------------------------------------------------------------------


def run_phase(name: str, fn, devices) -> dict:
    """Run one phase; its record, with any exception caught and recorded."""
    listen_for_compiles()
    t0 = time.perf_counter()
    try:
        rec = fn()
    except Exception:  # a failed phase is reported; the run goes on
        rec = {"correct": False, "error": traceback.format_exc()}
    wall = time.perf_counter() - t0
    comp = compile_seconds_since(t0)
    out = {"phase": name, "correct": bool(rec.pop("correct"))}
    out.update(wall_s=wall, compile_s=comp, run_s=wall - comp)
    out.update(rec)
    out["peak_bytes_in_use"] = peak_bytes_in_use(devices)
    return out


def single_chip_phases(wl: Workload, refs: References, backend: str = "pallas"):
    """The one-chip run's phases, in order: ``[(name, thunk)]``.

    ``backend`` is the kernel backend of every phase but the XLA twin; the
    CPU rehearsal passes ``"pallas-interpret"``.
    """
    from repro.core.placement import ffd_placement
    from repro.core.timing import TimeFunction
    from repro.graph.bsp import BSPTrace
    from repro.graph.config import EngineConfig
    from repro.graph.program import (
        BfsProgram,
        PageRankProgram,
        SsspProgram,
        WccProgram,
    )
    from repro.graph.session import open_session
    from repro.serve.service import ServiceConfig, TraversalService, poisson_trace

    cfg = EngineConfig(backend=backend, window=WINDOW)
    session = open_session(wl.pg, cfg)
    sources = [int(s) for s in wl.sources]
    kernel = backend != "xla"
    done = {}

    def traversal(sess, program, srcs):
        res = sess.run(program, srcs)
        rec = {"supersteps": int(np.max(res.n_supersteps))}
        if sess.config.backend != "xla":
            rec["kernel"] = dense_kernel_record(
                sess.pg, len(srcs), res.inner_iters, res.n_supersteps,
                program.stationary,
            )
        return res, rec

    def bfs():
        res, rec = traversal(session, BfsProgram(), sources)
        done["bfs"] = res
        rec["correct"] = bool(np.array_equal(res.dist, refs.bfs))
        return rec

    def sssp():
        res, rec = traversal(open_session(wl.wpg, cfg), SsspProgram(), sources)
        ok, err = sssp_correct(wl.wpg.graph, sources, res.dist, refs.sssp)
        rec.update(correct=ok, max_rel_err=err)
        return rec

    def wcc():
        res, rec = traversal(session, WccProgram(), sources[:1])
        rec["correct"] = bool(np.array_equal(res.dist[0], refs.wcc))
        rec["components"] = int(np.unique(res.dist[0]).size)
        return rec

    def pagerank():
        prog = PageRankProgram(PAGERANK_DAMPING, PAGERANK_ITERS)
        res, rec = traversal(session, prog, sources[:1])
        got = res.dist[0].astype(np.float64)
        err = float(np.max(np.abs(got - refs.pagerank) / refs.pagerank))
        rec.update(
            correct=err <= PAGERANK_RTOL
            and rec["supersteps"] == prog.superstep_budget,
            max_rel_err=err,
        )
        return rec

    def bfs_xla():
        xla = open_session(wl.pg, cfg.replace(backend="xla"))
        res, rec = traversal(xla, BfsProgram(), sources)
        ref = done["bfs"]
        same = all(
            np.array_equal(a, b) for a, b in zip(res, ref)
        )  # every TraversalResult field: state and counters
        rec.update(
            correct=same and bool(np.array_equal(res.dist, refs.bfs)),
            counters_identical_to=backend,
        )
        return rec

    def elastic():
        # plan from another source's trace, so replanning has work to do
        res = done["bfs"]
        m = int(res.n_supersteps[1])
        verts = res.verts_processed[1, :m]
        trace = BSPTrace(
            active=verts > 0,
            edges_examined=res.edges_examined[1, :m],
            verts_processed=verts,
            msgs_sent=res.msgs_sent[1, :m],
            inner_iters=res.inner_iters[1, :m],
            active_subgraphs=[],
        )
        plan = ffd_placement(TimeFunction.from_trace(trace))
        ex = session.executor(program=BfsProgram())
        rep = ex.run(sources[0], plan, strategy_fn=ffd_placement, replan=True)
        return {
            "correct": bool(np.array_equal(rep.dist, res.dist[0])),
            "supersteps": int(rep.n_supersteps),
            "replans": int(rep.replans),
            "host_syncs": int(rep.host_syncs),
            "window": int(rep.window),
        }

    def service():
        svc = TraversalService(
            wl.pg,
            config=ServiceConfig(s_batch=N_SOURCES, window=WINDOW),
            default_program=BfsProgram(),
            engine_config=cfg,
        )
        trace = poisson_trace(
            N_QUERIES, QUERY_RATE, wl.pg.graph.n_vertices, seed=QUERY_SEED
        )
        rep = svc.run(trace)
        return {
            "correct": rep.completed == N_QUERIES
            and rep.rejected == 0
            and rep.dropped == 0,
            "supersteps": int(rep.supersteps),
            "completed": int(rep.completed),
            "windows": int(rep.windows),
            "requeued": int(rep.requeued),
            "occupancy": float(rep.occupancy),
        }

    phases = [("bfs", bfs), ("sssp", sssp), ("wcc", wcc), ("pagerank", pagerank)]
    if kernel:
        phases.append(("bfs_xla", bfs_xla))
    phases += [("elastic", elastic), ("service", service)]
    return phases


def mesh_phases(wl: Workload, refs: References, n_devices: int,
                backend: str = "pallas"):
    """The ``--mesh`` run's phases: BFS and SSSP on the mesh engine, each
    with one mid-run swap of the partition -> device map."""
    from repro.dist.sharding import partition_mesh
    from repro.graph.config import EngineConfig
    from repro.graph.program import BfsProgram, SsspProgram
    from repro.graph.traversal import TraversalEngine

    cfg = EngineConfig(mesh=partition_mesh(n_devices), backend=backend)
    sources = [int(s) for s in wl.sources]

    def windowed(pg, program):
        # an engine of its own, not the graph's cached one: its sharded
        # arrays (both layouts, after the swap) are freed when the phase
        # ends, before the next phase uploads its own
        engine = TraversalEngine(pg, program=program, config=cfg)
        state = engine.init_state(sources)
        start_map = engine.device_of_part.copy()
        swap = np.roll(start_map, N_PARTS // n_devices)
        windows, swapped_at = 0, None
        while True:
            dop = None
            if windows == 1:
                dop, swapped_at = swap, int(np.max(wres.n_supersteps))
            wres = engine.run_window(state, MESH_WINDOW, device_of_part=dop)
            state = wres.state
            windows += 1
            if wres.done.all():
                break
        dist = engine.gather_global(np.asarray(state.dist))
        rec = {
            "supersteps": int(np.max(wres.n_supersteps)),
            "windows": windows,
            "relayout_at_superstep": swapped_at,
            "device_of_part": [start_map.tolist(), engine.device_of_part.tolist()],
        }
        relaid = swapped_at is not None and np.array_equal(
            engine.device_of_part, swap
        )
        return dist, rec, relaid

    def bfs():
        dist, rec, relaid = windowed(wl.pg, BfsProgram())
        rec["correct"] = relaid and bool(np.array_equal(dist, refs.bfs))
        return rec

    def sssp():
        dist, rec, relaid = windowed(wl.wpg, SsspProgram())
        ok, err = sssp_correct(wl.wpg.graph, sources, dist, refs.sssp)
        rec.update(correct=relaid and ok, max_rel_err=err)
        return rec

    return [("mesh_bfs", bfs), ("mesh_sssp", sssp)]


# -- entry -------------------------------------------------------------------


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--mesh", type=int, default=0, metavar="N",
        help="run only the mesh engine, over N chips",
    )
    args = ap.parse_args(argv)
    n_chips = max(1, args.mesh)
    present = tpu_devices(n_chips)
    devices = present[:n_chips]

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    t0 = time.perf_counter()
    wl = build_workload(SCALE)
    refs = build_references(wl, wcc_pagerank=not args.mesh)
    emit({
        "phase": "setup", "correct": True, "wall_s": time.perf_counter() - t0,
        "reference_s": refs.seconds, "compile_cache": cache_dir, **wl.setup,
    })
    phases = (
        mesh_phases(wl, refs, n_chips) if args.mesh
        else single_chip_phases(wl, refs)
    )
    ok = True
    for name, fn in phases:
        rec = run_phase(name, fn, devices)
        emit(rec)
        ok = ok and rec["correct"]
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    d = devices[0]
    emit({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(present),
    }})
    return 0


if __name__ == "__main__":
    sys.exit(main())
