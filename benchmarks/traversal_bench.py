"""Traversal-engine benchmark: device-resident batched BC vs the serial
per-superstep driver the seed shipped with, plus the windowed elastic
executor sweep and the mesh-sharding device sweep.

Measures, on a synthetic BC workload (>= 16 sources on an R-MAT graph):
  * serial driver  -- per-source Python superstep loop, one host sync
    (``np.asarray``) per superstep per source (the seed's ``run_sssp``
    orchestration, reproduced here as the baseline)
  * batched engine -- one jitted ``lax.while_loop`` over ``[S, n]`` state,
    one bulk transfer per traversal

for the elastic executor, a window-size sweep (``k in {1, 4, 8, 16}``)
on two graph shapes (power-law R-MAT vs uniform Erdos-Renyi): host-sync
counts per run, the ``ceil(S/k) + 1`` sync-budget check at ``k=8``, and the
windowed-vs-per-superstep wall speedup,

and, for the mesh-sharded engine, a device sweep (``D in {1, 2, 4, 8}`` on
8 forced host devices, run in a subprocess because the XLA device-count flag
must precede jax init): per-superstep messages on the wire *post*
per-destination aggregation vs the *pre*-aggregation active-remote-edge
count -- the D>1 rows assert that aggregation genuinely shrinks the
collective payload.  Run this file with ``--mesh-child`` to produce just
that sweep as JSON on stdout (what the parent process invokes).

The ``--programs`` sweep (also part of the full run) exercises the
VertexProgram algebra on a weighted twin of the benchmark graph: for each of
bfs / sssp / wcc / pagerank it records dense supersteps/sec, the wire-message
saving of per-destination combiner aggregation on an 8-device mesh (its own
forced-device subprocess, ``--programs-child``), and the elastic
(ffd-planned) vs static (default placement) billing of the program's own
executed trace.  The stationary pagerank row is the designed contrast case:
``mean_active_fraction == 1`` (no activation sparsity for elasticity to
harvest -- its ffd savings are pure load consolidation), versus the sweeping
partial-activation profiles of the traversals.  ``--programs`` alone merges
just this sweep into an existing ``BENCH_traversal.json``.

The ``--relayout`` sweep (the paper's Table-style comparison for dynamic
re-layout) runs the ffd-planned elastic executor twice per mesh size
(D in {2, 8}, forced-device subprocess): static compute layout vs
``relayout=True`` (compute follows the planner).  Recorded per D: billed
cost/makespan/migration (asserted *identical* -- the economics must not
depend on the compute layout), the physical device-move ledger (re-layout
pays real remap bytes the static layout doesn't), re-layout count, and the
residency-follows-plan check.  ``--relayout`` alone merges just this sweep
into an existing ``BENCH_traversal.json``.

The ``--kernel-path`` sweep (also part of the full run) times the dense
engine per program under ``backend="xla"`` vs ``backend="pallas-interpret"``
(the block-skipping relax kernels through the Pallas interpreter -- the CPU
parity mode, expected slower than XLA here) and asserts backend parity per
row; projected TPU per-call cost comes from ``benchmarks.kernel_bench``'s
roofline model and is attached to the section.  ``--kernel-path`` alone
merges just this sweep into an existing ``BENCH_traversal.json``.

The ``--mirror`` sweep (hub-vertex mirroring, also part of the full run)
compares the mirrored mesh engine (``mirror_degree`` in ``MIRROR_DEGREES``)
against the unmirrored path at D=8 on a denser weighted R-MAT twin
(avg degree ``MIRROR_RMAT_DEGREE``, where hub fan-in dominates): per
(threshold, program) it asserts result parity in-run (bit-identical state +
counters for the min-programs, counters-exact/state-allclose for PageRank)
and records wire slots/bytes per superstep both ways; the child asserts the
>= 25% best-case reduction the acceptance bar requires.  ``--mirror`` alone
merges just this sweep into an existing ``BENCH_traversal.json``.

The ``--serving`` sweep (the ``repro.serve`` subsystem, also part of the
full run) replays a seeded open-loop Poisson ``TraversalQuery`` trace at
several arrival rates through ``TraversalService`` twice per rate -- elastic
per-window VM capacity (activity forecast + queue-drift rule) vs statically
provisioned at ``max_vms`` -- and records throughput, sojourn percentiles,
occupancy, billed quanta and cost per 1k queries for both.  The sweep
asserts the elastic acceptance bar in-run: at >= 1 rate elastic must beat
static on cost per 1k queries while keeping p99 sojourn within 2x of
static.  Everything runs off the service's simulated clock, so the rows are
bit-for-bit reproducible.  ``--serving`` alone merges just this sweep into
an existing ``BENCH_traversal.json``.

The ``--dynamic`` sweep (streaming graph mutations, also part of the full
run) replays a seeded "migrant vertex" workload -- at each mutation epoch a
fraction of vertices (the rate) gains ``DYNAMIC_MIGRANT_EDGES`` new edges
into one far partition, delivered as ``EdgeDeltaBuffer``s merged at window
boundaries mid-traversal -- through the elastic executor twice per rate:
partition map frozen vs incrementally repartitioned (bounded LPA pass at
every merge).  Both runs must converge to the *same* distances (asserted:
repartitioning relocates computation, never changes results).  Recorded per
rate and map policy: the mirror-aware partition penalty of the final map,
repartition moves, the executor's own billed quanta, and the steady-state
elastic serving cost of the mutated graph -- a BFS trace whose tau carries a
wire term (``DYNAMIC_MSG_COST`` seconds per remote message), ffd-planned and
billed at a fine quantum.  The staleness-vs-throughput tradeoff the section
exists to show: a frozen map keeps paying the wire term on every migrant
edge forever, so at nonzero rates the repartitioned map must win on penalty
strictly and on steady-state elastic cost (strictly at >= 1 rate) -- both
asserted in-run and by the CI schema check.  ``--dynamic`` alone merges just
this sweep into an existing ``BENCH_traversal.json``.

``--serve-smoke`` is the serving CI gate (dense engine, in-process, no
forced devices): a tiny-graph fixed-seed trace served elastic and static,
asserting throughput > 0, finite p99 sojourn, elastic billed cost <= static,
and deterministic replay (two ``service.run(trace)`` calls return equal
reports).

``--smoke`` is the CI gate: on a tiny graph it asserts the wire-savings and
elastic-vs-static invariants (plus relayout bit-identity, xla vs
pallas-interpret mesh parity, mirrored-vs-unmirrored parity with strictly
fewer wire slots, the delta-merge byte-identity -- merged layout ==
from-scratch build of the mutated graph, field by field -- and the
repartitioned-penalty/cost-never-worse pair) in a short forced-device
child, and schema-checks the *committed* ``BENCH_traversal.json`` (parses;
has the ``mesh_sweep`` / ``program_sweep`` / ``relayout`` / ``kernel_path``
/ ``mirror_sweep`` / ``serving`` / ``dynamic`` sections, with every
kernel-path row
recording ``parity_ok``, the mirror sweep clearing the 25% bar, and the
serving sweep clearing its cost/latency acceptance bar) -- without
rewriting the file.

Writes ``BENCH_traversal.json`` so the perf trajectory is tracked per PR.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro.core.billing import BillingModel, evaluate
from repro.core.elastic import ElasticBSPExecutor
from repro.core.placement import default_placement, ffd_placement
from repro.core.timing import DEFAULT_ALPHA, DEFAULT_BETA, TimeFunction
from repro.graph.bsp import run_bc_forward, run_program, run_sssp
from repro.graph.config import EngineConfig
from repro.graph.generators import erdos_renyi_graph, rmat_graph, weighted
from repro.graph.partition import bfs_grow_partition
from repro.graph.program import BUILTIN_PROGRAMS, PageRankProgram
from repro.graph.structs import PartitionedGraph
from repro.graph.traversal import make_superstep_fn

N_SOURCES = 16
SCALE, DEGREE = 12, 8  # R-MAT 2^12 vertices, avg degree 8
N_PARTS = 8
WINDOW_SIZES = (1, 4, 8, 16)
MESH_SIZES = (1, 2, 4, 8)
RELAYOUT_MESH_SIZES = (2, 8)
MESH_FORCED_DEVICES = 8
PAGERANK_ITERS = 20
MIRROR_DEGREES = (2, 4, 8)  # hub in-degree thresholds swept by --mirror
MIRROR_MESH_D = 8
#: avg degree of the mirror sweep's own R-MAT twin.  Mirror-cache
#: suppression is a fan-in effect -- a (device, hub) slot is *touched*
#: nearly every superstep but *improves* rarely when many remote edges feed
#: it -- so the sweep measures on a denser graph than the placement
#: benchmarks, where hub traffic actually dominates the wire.
MIRROR_RMAT_DEGREE = 16
OUT_PATH = "BENCH_traversal.json"
#: sections the committed JSON must carry (CI schema check)
REQUIRED_SECTIONS = (
    "mesh_sweep", "program_sweep", "relayout", "kernel_path", "mirror_sweep",
    "serving", "dynamic",
)
#: serving sweep shape (see repro.serve): arrival rates are in queries per
#: simulated second; tau_scale keeps the whole busy span of a run inside one
#: billing quantum so elastic consolidation shows up in billed quanta
SERVE_SCALE, SERVE_DEGREE, SERVE_PARTS = 9, 8, 8
SERVE_RATES = (5.0, 20.0, 80.0)
SERVE_QUERIES = 120
SERVE_TAU_SCALE = 1e3
#: elastic acceptance bar: at >= 1 rate, cost/1k win with p99 within this
SERVE_P99_STRETCH = 2.0
#: dynamic-graph sweep shape: per mutation epoch, ``rate * n`` migrant
#: vertices each gain ``DYNAMIC_MIGRANT_EDGES`` edges (both directions) into
#: one far partition.  ``DYNAMIC_MSG_COST`` prices a remote message into the
#: steady-state tau (the wire term a stale partition map keeps paying);
#: ``DYNAMIC_DELTA`` is the fine billing quantum that makes the resulting
#: cost difference visible in integer quanta.
DYNAMIC_SCALE, DYNAMIC_DEGREE, DYNAMIC_PARTS = 10, 8, 8
DYNAMIC_RATES = (0.0, 0.01, 0.04)  # migrant fraction of the vertex set
DYNAMIC_EPOCHS = 3
DYNAMIC_MIGRANT_EDGES = 12
DYNAMIC_MSG_COST = 1e-6
DYNAMIC_DELTA = 1e-6
DYNAMIC_WINDOW = 1  # every superstep a boundary: merges land mid-traversal
DYNAMIC_MAX_MOVES = 96
DYNAMIC_BALANCE = 1.25


def _bench_programs():
    """One instance per builtin program, pagerank pinned to the bench budget."""
    return {
        name: (
            PageRankProgram(num_iters=PAGERANK_ITERS)
            if name == "pagerank"
            else ctor()
        )
        for name, ctor in BUILTIN_PROGRAMS.items()
    }


def _weighted_bench_pg() -> PartitionedGraph:
    """Weighted twin of the benchmark graph, same partition map (weights do
    not influence partitioning, so the partition structure stays comparable
    across the sweeps)."""
    g = rmat_graph(SCALE, DEGREE, seed=3)
    pg = bfs_grow_partition(g, N_PARTS, seed=1)
    return PartitionedGraph(weighted(g, seed=5), N_PARTS, pg.part_of_vertex)


def _mirror_bench_pg() -> PartitionedGraph:
    """Denser weighted R-MAT for the hub-mirroring sweep (same scale and
    seeds as the bench graph, avg degree ``MIRROR_RMAT_DEGREE``): the
    power-law hub fan-in that mirroring harvests."""
    g = rmat_graph(SCALE, MIRROR_RMAT_DEGREE, seed=3)
    pg = bfs_grow_partition(g, N_PARTS, seed=1)
    return PartitionedGraph(weighted(g, seed=5), N_PARTS, pg.part_of_vertex)


def _serial_bc(pg, sources):
    """The seed's orchestration: Python superstep loop, host sync per step.

    Returns (n_supersteps_total, n_host_syncs).
    """
    superstep = make_superstep_fn(pg)
    n = pg.graph.n_vertices
    total_steps = 0
    syncs = 0
    for source in sources:
        dist = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
        frontier = jnp.zeros((n,), bool).at[source].set(True)
        while True:
            fr_np = np.asarray(frontier)  # the per-superstep host round-trip
            syncs += 1
            if not fr_np.any():
                break
            res = superstep(dist, frontier)
            dist, frontier = res.dist, res.next_frontier
            # counter pulls, as the seed driver did every superstep
            _ = np.asarray(res.edges_examined)
            _ = np.asarray(res.verts_processed)
            _ = np.asarray(res.msgs_sent)
            syncs += 3
            total_steps += 1
    return total_steps, syncs


def _window_sweep(pg, source: int = 0) -> dict:
    """Elastic-executor window sweep on one partitioned graph: wall time and
    host syncs per window size, same ffd plan throughout."""
    _, trace = run_sssp(pg, source, collect_subgraphs=False)
    plan = ffd_placement(TimeFunction.from_trace(trace))
    per_k = {}
    for k in WINDOW_SIZES:
        ex = ElasticBSPExecutor(pg, config=EngineConfig(window=k))
        ex.run(source, plan)  # warm (compile) this window depth
        t0 = time.perf_counter()
        rep = ex.run(source, plan)
        wall = time.perf_counter() - t0
        per_k[str(k)] = {
            "wall_s": wall,
            "host_syncs": rep.host_syncs,
            "supersteps": rep.n_supersteps,
        }
    s = per_k["8"]["supersteps"]
    return {
        "n_vertices": pg.graph.n_vertices,
        "n_edges": pg.graph.n_edges,
        "n_parts": pg.n_parts,
        "windows": per_k,
        "speedup_w8_vs_w1": per_k["1"]["wall_s"] / per_k["8"]["wall_s"],
        "sync_budget_w8_ok": per_k["8"]["host_syncs"] <= math.ceil(s / 8) + 1,
    }


def _mesh_child() -> dict:
    """The device sweep body; runs under forced host devices (subprocess).

    For each mesh size D: one batched traversal on the mesh-sharded engine,
    recording per-superstep wire messages (post per-destination aggregation,
    summed over sources and devices) against the pre-aggregation active
    remote-edge count.  Asserts the reduction for every D > 1.
    """
    import jax

    from repro.dist.sharding import partition_mesh
    from repro.graph.traversal import get_engine

    assert len(jax.devices()) >= max(MESH_SIZES), (
        f"mesh child needs {max(MESH_SIZES)} devices, has {len(jax.devices())}"
    )
    g = rmat_graph(SCALE, DEGREE, seed=3)
    pg = bfs_grow_partition(g, N_PARTS, seed=1)
    rng = np.random.default_rng(0)
    sources = rng.choice(g.n_vertices, size=4, replace=False).tolist()

    per_d = {}
    for d_n in MESH_SIZES:
        eng = get_engine(
            pg, config=EngineConfig(m_max=512, mesh=partition_mesh(d_n)),
        )
        eng.run(sources)  # warm (compile)
        t0 = time.perf_counter()
        res = eng.run(sources)
        wall = time.perf_counter() - t0
        m = int(res.n_supersteps.max())
        wire = res.wire_msgs[:, :m].sum(axis=0)  # [m] over sources
        pre = res.msgs_sent[:, :m].sum(axis=(0, 2))  # [m] over sources/parts
        wire_total, pre_total = int(wire.sum()), int(pre.sum())
        if d_n > 1:
            assert 0 < wire_total < pre_total, (
                f"D={d_n}: per-destination aggregation must put fewer "
                f"messages on the wire than the raw active-remote-edge "
                f"count ({wire_total} vs {pre_total})"
            )
        per_d[str(d_n)] = {
            "wall_s": wall,
            "supersteps": m,
            "wire_per_superstep": [int(x) for x in wire],
            "pre_agg_per_superstep": [int(x) for x in pre],
            "wire_total": wire_total,
            "pre_agg_total": pre_total,
            "wire_reduction": (
                None if wire_total == 0 else 1.0 - wire_total / pre_total
            ),
        }
    return {
        "n_devices_forced": MESH_FORCED_DEVICES,
        "n_sources": len(sources),
        "graph": {
            "n_vertices": g.n_vertices,
            "n_edges": g.n_edges,
            "n_parts": N_PARTS,
        },
        "per_d": per_d,
    }


def _mesh_sweep_subprocess() -> dict:
    """Run ``--mesh-child`` with the XLA device-count flag in a fresh
    process (the flag is dead after jax initializes, hence the subprocess)."""
    from repro.testing.forced_devices import run_forced_devices

    out = run_forced_devices(
        os.path.abspath(__file__),
        "--mesh-child",
        n_devices=MESH_FORCED_DEVICES,
        timeout=1800,
    )
    return json.loads(out)


def _programs_child() -> dict:
    """Per-program wire-message accounting on an 8-device mesh (subprocess
    body): post-aggregation wire slots vs raw active remote edges, per
    builtin VertexProgram, on the weighted benchmark graph."""
    import jax

    from repro.dist.sharding import partition_mesh
    from repro.graph.traversal import get_engine

    assert len(jax.devices()) >= MESH_FORCED_DEVICES
    pg = _weighted_bench_pg()
    mesh = partition_mesh(MESH_FORCED_DEVICES)
    rows = {}
    for name, prog in _bench_programs().items():
        res = get_engine(
            pg, program=prog, config=EngineConfig(m_max=512, mesh=mesh),
        ).run([0])
        wire, pre = int(res.wire_msgs.sum()), int(res.msgs_sent.sum())
        assert 0 < wire < pre, (
            f"{name}: combiner aggregation must shrink the wire "
            f"({wire} vs {pre})"
        )
        rows[name] = {
            "wire_total": wire,
            "pre_agg_total": pre,
            "wire_reduction": 1.0 - wire / pre,
        }
    return {"n_devices": MESH_FORCED_DEVICES, "per_program": rows}


def _program_sweep() -> dict:
    """The VertexProgram sweep: per program, dense supersteps/sec, mesh wire
    savings (subprocess), and the elastic-vs-static billing of the program's
    own executed trace."""
    from repro.testing.forced_devices import run_forced_devices

    pg = _weighted_bench_pg()
    model = BillingModel()
    rows = {}
    for name, prog in _bench_programs().items():
        run_program(pg, prog, [0], max_supersteps=512)  # warm (compile)
        t0 = time.perf_counter()
        _, traces = run_program(pg, prog, [0], max_supersteps=512)
        wall = time.perf_counter() - t0
        trace = traces[0]
        tf = TimeFunction.from_trace(trace)
        elastic = evaluate(ffd_placement(tf), model)
        static = evaluate(default_placement(tf), model)
        rows[name] = {
            "supersteps": int(trace.n_supersteps),
            "wall_s": wall,
            "supersteps_per_sec": trace.n_supersteps / wall,
            "mean_active_fraction": trace.mean_active_fraction(),
            "elastic_cost_quanta": int(elastic.cost_quanta),
            "static_cost_quanta": int(static.cost_quanta),
            "elastic_saving_vs_static": (
                1.0 - elastic.cost_quanta / static.cost_quanta
            ),
        }
    wire = json.loads(
        run_forced_devices(
            os.path.abspath(__file__),
            "--programs-child",
            n_devices=MESH_FORCED_DEVICES,
            timeout=1800,
        )
    )
    for name, row in wire["per_program"].items():
        rows[name].update(row)
    return {
        "graph": "weighted rmat",
        "n_parts": N_PARTS,
        "pagerank_iters": PAGERANK_ITERS,
        "per_program": rows,
    }


def _kernel_path_sweep() -> dict:
    """Compute-backend sweep on the dense engine: per builtin program, wall
    time and parity of ``backend="pallas-interpret"`` (the block-skipping
    relax kernels through the Pallas interpreter) against ``backend="xla"``
    (the segment-op default).

    The interpreter is a semantics check, not a speed path -- on CPU it is
    expected to be *slower* than XLA; what a TPU run would pay is captured
    by the roofline projections from ``benchmarks.kernel_bench`` attached as
    ``roofline``.  ``parity_ok`` per row asserts bit-identical counters for
    every program plus bit-identical state for min-programs (rounding-equal
    for the float sum path).
    """
    from benchmarks.kernel_bench import run as kernel_bench_run
    from repro.graph.traversal import get_engine

    pg = _weighted_bench_pg()
    rows = {}
    for name, prog_proto in _bench_programs().items():
        per_backend = {}
        results = {}
        for backend in ("xla", "pallas-interpret"):
            prog = (
                PageRankProgram(num_iters=PAGERANK_ITERS)
                if name == "pagerank"
                else BUILTIN_PROGRAMS[name]()
            )
            eng = get_engine(
                pg, program=prog, config=EngineConfig(m_max=512, backend=backend),
            )
            eng.run([0])  # warm (compile)
            t0 = time.perf_counter()
            res = eng.run([0])
            per_backend[backend] = time.perf_counter() - t0
            results[backend] = res
        rx, rk = results["xla"], results["pallas-interpret"]
        counters_ok = all(
            np.array_equal(np.asarray(getattr(rx, f)), np.asarray(getattr(rk, f)))
            for f in (
                "edges_examined", "verts_processed", "msgs_sent",
                "inner_iters", "wire_msgs", "n_supersteps",
            )
        )
        if prog_proto.reduce == "min":
            state_ok = np.array_equal(np.asarray(rx.dist), np.asarray(rk.dist))
        else:
            state_ok = bool(
                np.allclose(
                    np.asarray(rk.dist), np.asarray(rx.dist),
                    rtol=1e-5, atol=1e-9,
                )
            )
        rows[name] = {
            "xla_wall_s": per_backend["xla"],
            "pallas_interpret_wall_s": per_backend["pallas-interpret"],
            "supersteps": int(rx.n_supersteps.max()),
            "parity_ok": bool(counters_ok and state_ok),
        }
        assert rows[name]["parity_ok"], f"kernel path parity broken: {name}"
    return {
        "graph": "weighted rmat",
        "note": (
            "pallas-interpret is the CPU parity mode (interpreter overhead "
            "included); projected TPU cost per kernel call is in roofline"
        ),
        "per_program": rows,
        "roofline": kernel_bench_run(verbose=False),
    }


def run_kernel_path_only(verbose: bool = True) -> dict:
    """``--kernel-path``: compute just the backend sweep and merge it into an
    existing ``BENCH_traversal.json`` (fresh file if none)."""
    out = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            out = json.load(f)
    out["kernel_path"] = _kernel_path_sweep()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        _print_kernel_path_sweep(out["kernel_path"])
        print(f"-> {OUT_PATH}")
    return out


def _print_kernel_path_sweep(sweep: dict) -> None:
    for name, row in sweep["per_program"].items():
        print(
            f"kernel path {name}: xla {row['xla_wall_s']*1e3:.0f} ms vs "
            f"pallas-interpret {row['pallas_interpret_wall_s']*1e3:.0f} ms "
            f"over {row['supersteps']} supersteps, parity "
            f"{'OK' if row['parity_ok'] else 'BROKEN'}"
        )
    for r in sweep["roofline"]:
        print(
            f"  roofline {r['name']}: {r['roofline_us']:.1f} us/call "
            f"({r['bound']}-bound, {r['vmem_mib']:.2f} MiB VMEM)"
        )


def _relayout_run(pg, plan, mesh, *, relayout: bool, window: int = 8) -> dict:
    """One warmed elastic run; returns its ledger row (plus dist for the
    caller's bit-identity assert)."""
    ex = ElasticBSPExecutor(
        pg, config=EngineConfig(mesh=mesh, window=window, relayout=relayout)
    )
    ex.run(0, plan)  # warm (compile)
    t0 = time.perf_counter()
    rep = ex.run(0, plan)
    wall = time.perf_counter() - t0
    return {
        "wall_s": wall,
        "supersteps": int(rep.n_supersteps),
        "makespan": float(rep.cost.makespan),
        "cost_quanta": int(rep.cost.cost_quanta),
        "migration_secs": float(rep.cost.migration_secs),
        "n_migrations": int(rep.n_migrations),
        "device_moves": int(rep.device_moves),
        "device_move_bytes": int(rep.device_move_bytes),
        "relayouts": int(rep.relayouts),
        "_dist": rep.dist,
        "_residency": rep.residency,
    }


def _relayout_pair(pg, plan, d_n: int, *, window: int = 8) -> dict:
    """Static-layout vs dynamic-relayout elastic runs on a D-device mesh:
    asserts bit-identical dist and identical *billed* economics, and that
    re-layout actually computes on the planned devices."""
    from repro.dist.sharding import partition_mesh

    mesh = partition_mesh(d_n)
    static = _relayout_run(pg, plan, mesh, relayout=False, window=window)
    dynamic = _relayout_run(pg, plan, mesh, relayout=True, window=window)
    assert (static.pop("_dist") == dynamic.pop("_dist")).all(), (
        f"D={d_n}: dynamic re-layout changed the result"
    )
    static.pop("_residency")
    res = dynamic.pop("_residency")
    for key in ("makespan", "cost_quanta", "migration_secs", "n_migrations"):
        assert static[key] == dynamic[key], (
            f"D={d_n}: billed {key} must not depend on the compute layout "
            f"({static[key]} vs {dynamic[key]})"
        )
    # residency follows the plan: at each window boundary every *placed*
    # partition computes on its planned device
    s = 0
    for w in range(res.shape[0]):
        if s >= plan.vm_of.shape[0]:
            break
        row = plan.vm_of[s]
        placed = row >= 0
        assert (res[w][placed] == row[placed] % d_n).all(), (
            f"D={d_n} window {w}: partitions not computing on planned devices"
        )
        s += window
    return {
        "static": static,
        "dynamic": dynamic,
        "billing_identical": True,
        "residency_follows_plan": True,
    }


def _relayout_child() -> dict:
    """Forced-device subprocess body for the dynamic re-layout sweep."""
    import jax

    assert len(jax.devices()) >= max(RELAYOUT_MESH_SIZES)
    g = rmat_graph(SCALE, DEGREE, seed=3)
    pg = bfs_grow_partition(g, N_PARTS, seed=1)
    _, trace = run_sssp(pg, 0)
    plan = ffd_placement(TimeFunction.from_trace(trace))
    # window=1 puts a placement point at every superstep (the paper's
    # granularity) so the plan's consolidation actually exercises swaps
    per_d = {
        str(d_n): _relayout_pair(pg, plan, d_n, window=1)
        for d_n in RELAYOUT_MESH_SIZES
    }
    assert any(r["dynamic"]["relayouts"] > 0 for r in per_d.values()), (
        "relayout sweep never swapped a layout -- comparison is vacuous"
    )
    return {"n_parts": N_PARTS, "window": 1, "per_d": per_d}


def _relayout_sweep_subprocess() -> dict:
    from repro.testing.forced_devices import run_forced_devices

    out = run_forced_devices(
        os.path.abspath(__file__),
        "--relayout-child",
        n_devices=MESH_FORCED_DEVICES,
        timeout=1800,
    )
    return json.loads(out)


def _print_relayout_sweep(sweep: dict) -> None:
    for d_n, row in sweep["per_d"].items():
        st, dy = row["static"], row["dynamic"]
        print(
            f"relayout D={d_n}: billed cost {st['cost_quanta']} quanta / "
            f"makespan {st['makespan']:.3g}s identical static vs dynamic; "
            f"physical moves {st['device_moves']} -> {dy['device_moves']} "
            f"({dy['device_move_bytes']} B, {dy['relayouts']} re-layouts), "
            f"residency follows plan: {row['residency_follows_plan']}"
        )


def run_relayout_only(verbose: bool = True) -> dict:
    """``--relayout``: compute just the re-layout sweep and merge it into an
    existing ``BENCH_traversal.json`` (fresh file if none)."""
    out = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            out = json.load(f)
    out["relayout"] = _relayout_sweep_subprocess()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        _print_relayout_sweep(out["relayout"])
        print(f"-> {OUT_PATH}")
    return out


# -- hub-mirroring sweep ------------------------------------------------------

_PARITY_COUNTERS = (
    "n_supersteps", "edges_examined", "verts_processed", "msgs_sent",
    "inner_iters",
)


def _assert_mirror_parity(name, prog, r0, r1, ctx=""):
    """Mirroring is an optimisation, not an algorithm change: every counter
    bit-identical for all programs, state bit-identical for min-programs
    and rounding-equal for the stationary sum (the mirror combine
    reassociates float adds, same convention as dense-vs-mesh)."""
    for f in _PARITY_COUNTERS:
        assert np.array_equal(
            np.asarray(getattr(r1, f)), np.asarray(getattr(r0, f))
        ), f"{ctx}{name}: counter {f} diverged under mirroring"
    if prog.reduce == "min":
        assert np.array_equal(np.asarray(r1.dist), np.asarray(r0.dist)), (
            f"{ctx}{name}: mirrored state not bit-identical"
        )
        return "bit-identical"
    assert np.allclose(
        np.asarray(r1.dist), np.asarray(r0.dist), rtol=1e-5, atol=1e-9
    ), f"{ctx}{name}: mirrored state out of tolerance"
    return "counters-exact,state-allclose"


def _mirror_child() -> dict:
    """Hub-mirroring sweep body (forced-device subprocess): per hub
    threshold x builtin program at D=8 on the weighted R-MAT bench graph,
    mirrored-vs-unmirrored parity asserted in-run, wire slots/bytes per
    superstep recorded.  Min-programs must save wire (mirror-cache
    suppression); the stationary program's wire billing is unchanged by
    design (its mirror aggregates sync every superstep)."""
    import jax

    from repro.dist.sharding import partition_mesh
    from repro.graph.traversal import get_engine

    assert len(jax.devices()) >= MIRROR_MESH_D
    pg = _mirror_bench_pg()
    mesh = partition_mesh(MIRROR_MESH_D)
    base = {
        name: get_engine(
            pg, program=prog, config=EngineConfig(m_max=512, mesh=mesh),
        ).run([0])
        for name, prog in _bench_programs().items()
    }
    per_degree = {}
    best = None
    for t in MIRROR_DEGREES:
        rows = {}
        for name, prog in _bench_programs().items():
            r0 = base[name]
            r1 = get_engine(
                pg,
                program=prog,
                config=EngineConfig(m_max=512, mesh=mesh, mirror_degree=t),
            ).run([0])
            parity = _assert_mirror_parity(name, prog, r0, r1, f"degree {t}: ")
            m = int(np.asarray(r0.n_supersteps).max())
            w0 = int(np.asarray(r0.wire_msgs).sum())
            w1 = int(np.asarray(r1.wire_msgs).sum())
            itemsize = int(np.dtype(prog.dtype).itemsize)
            reduction = None if w0 == 0 else 1.0 - w1 / w0
            rows[name] = {
                "supersteps": m,
                "wire_total_unmirrored": w0,
                "wire_total_mirrored": w1,
                "wire_slots_per_superstep_unmirrored": w0 / m,
                "wire_slots_per_superstep_mirrored": w1 / m,
                "wire_bytes_per_superstep_unmirrored": w0 * itemsize / m,
                "wire_bytes_per_superstep_mirrored": w1 * itemsize / m,
                "wire_reduction": reduction,
                "parity": parity,
            }
            if prog.reduce == "min":
                assert 0 < w1 < w0, (
                    f"degree {t}: {name} must put strictly fewer slots on "
                    f"the wire ({w1} vs {w0})"
                )
                if best is None or reduction > best["wire_reduction"]:
                    best = {
                        "program": name,
                        "mirror_degree": t,
                        "wire_reduction": reduction,
                    }
            else:
                assert w1 == w0, (
                    f"degree {t}: {name} wire billing changed ({w1} vs {w0})"
                )
        per_degree[str(t)] = rows
    assert best is not None and best["wire_reduction"] >= 0.25, (
        f"acceptance: mirroring must cut wire slots/superstep by >= 25% at "
        f"D={MIRROR_MESH_D}; best was {best}"
    )
    return {
        "n_devices": MIRROR_MESH_D,
        "graph": f"weighted rmat 2^{SCALE} avg degree {MIRROR_RMAT_DEGREE}",
        "mirror_degrees": list(MIRROR_DEGREES),
        "per_degree": per_degree,
        "best": best,
    }


def _mirror_sweep_subprocess() -> dict:
    from repro.testing.forced_devices import run_forced_devices

    out = run_forced_devices(
        os.path.abspath(__file__),
        "--mirror-child",
        n_devices=MESH_FORCED_DEVICES,
        timeout=1800,
    )
    return json.loads(out)


def _print_mirror_sweep(sweep: dict) -> None:
    for t, rows in sweep["per_degree"].items():
        for name, row in rows.items():
            red = row["wire_reduction"]
            print(
                f"mirror degree>={t} {name}: "
                f"{row['wire_slots_per_superstep_unmirrored']:.0f} -> "
                f"{row['wire_slots_per_superstep_mirrored']:.0f} "
                f"slots/superstep"
                + (f" ({red:.0%} saved)" if red else "")
                + f", parity {row['parity']}"
            )
    b = sweep["best"]
    print(
        f"mirror best: {b['program']} at degree>={b['mirror_degree']} saves "
        f"{b['wire_reduction']:.0%} of wire slots/superstep at D="
        f"{sweep['n_devices']}"
    )


def run_mirror_only(verbose: bool = True) -> dict:
    """``--mirror``: compute just the hub-mirroring sweep and merge it into
    an existing ``BENCH_traversal.json`` (fresh file if none)."""
    out = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            out = json.load(f)
    out["mirror_sweep"] = _mirror_sweep_subprocess()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        _print_mirror_sweep(out["mirror_sweep"])
        print(f"-> {OUT_PATH}")
    return out


# -- elastic serving sweep ----------------------------------------------------


def _serve_row(rep) -> dict:
    """One ServiceReport flattened to the bench JSON row."""
    return {
        "completed": rep.completed,
        "rejected": rep.rejected,
        "requeued": rep.requeued,
        "queries_per_sec": rep.queries_per_sec,
        "sojourn_p50": rep.sojourn_p50,
        "sojourn_p95": rep.sojourn_p95,
        "sojourn_p99": rep.sojourn_p99,
        "occupancy": rep.occupancy,
        "capacity_mean": rep.capacity_mean,
        "capacity_peak": rep.capacity_peak,
        "cost_quanta": rep.cost.cost_quanta,
        "cost_per_1k_queries": rep.cost_per_1k_queries,
    }


def _serving_sweep() -> dict:
    """Open-loop Poisson serving at ``SERVE_RATES``: elastic vs static
    ``TraversalService`` runs on the same seeded trace per rate (see module
    docstring).  Asserts the elastic acceptance bar in-run."""
    import dataclasses

    from repro.graph.partition import hash_partition
    from repro.serve import ServiceConfig, TraversalService, poisson_trace

    g = rmat_graph(SERVE_SCALE, SERVE_DEGREE, seed=0)
    pg = hash_partition(g, SERVE_PARTS, seed=0)
    cfg = ServiceConfig(s_batch=8, window=8, tau_scale=SERVE_TAU_SCALE)
    static_cfg = dataclasses.replace(cfg, static_vms=cfg.max_vms)
    per_rate = {}
    bar_met = False
    for rate in SERVE_RATES:
        trace = poisson_trace(SERVE_QUERIES, rate, g.n_vertices, seed=0)
        elastic = TraversalService(pg, config=cfg).run(trace)
        static = TraversalService(pg, config=static_cfg).run(trace)
        p99_ratio = (
            elastic.sojourn_p99 / static.sojourn_p99
            if static.sojourn_p99 > 0
            else 1.0
        )
        cost_win = elastic.cost_per_1k_queries < static.cost_per_1k_queries
        if cost_win and p99_ratio <= SERVE_P99_STRETCH:
            bar_met = True
        per_rate[str(rate)] = {
            "elastic": _serve_row(elastic),
            "static": _serve_row(static),
            "p99_ratio_elastic_vs_static": p99_ratio,
            "elastic_cost_win": cost_win,
        }
    assert bar_met, (
        f"serving acceptance: no rate in {SERVE_RATES} has elastic beating "
        f"static on cost/1k with p99 within {SERVE_P99_STRETCH}x"
    )
    return {
        "graph": f"rmat 2^{SERVE_SCALE} avg degree {SERVE_DEGREE}",
        "n_parts": SERVE_PARTS,
        "n_queries": SERVE_QUERIES,
        "tau_scale": SERVE_TAU_SCALE,
        "rates": list(SERVE_RATES),
        "s_batch": cfg.s_batch,
        "window": cfg.window,
        "vm_range": [cfg.min_vms, cfg.max_vms],
        "p99_stretch_bar": SERVE_P99_STRETCH,
        "per_rate": per_rate,
    }


def _print_serving_sweep(sweep: dict) -> None:
    for rate, row in sweep["per_rate"].items():
        e, s = row["elastic"], row["static"]
        print(
            f"serving rate {rate}: elastic {e['queries_per_sec']:.1f} qps, "
            f"{e['cost_quanta']} quanta ({e['cost_per_1k_queries']:.0f}/1k) "
            f"vs static {s['cost_quanta']} quanta "
            f"({s['cost_per_1k_queries']:.0f}/1k), p99 ratio "
            f"{row['p99_ratio_elastic_vs_static']:.2f}"
            + (" [cost win]" if row["elastic_cost_win"] else "")
        )


def run_serving_only(verbose: bool = True) -> dict:
    """``--serving``: compute just the serving sweep and merge it into an
    existing ``BENCH_traversal.json`` (fresh file if none)."""
    out = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            out = json.load(f)
    out["serving"] = _serving_sweep()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        _print_serving_sweep(out["serving"])
        print(f"-> {OUT_PATH}")
    return out


# -- dynamic graphs: streaming mutations vs incremental repartitioning -------


def _dynamic_mutations(pg, rate: float, seed: int) -> list:
    """Seeded migrant workload: per epoch, ``rate * n`` vertices each gain
    ``DYNAMIC_MIGRANT_EDGES`` edges (inserted in both directions) into one
    uniformly chosen *other* partition.  A migrant's new cross-degree
    exceeds its original local degree, so the neighbor-majority
    repartitioner has a strict incentive to move it -- a frozen map pays the
    wire term on every new edge forever.  Returns the executor's
    ``mutations=`` feed: ``[(due_superstep, EdgeDeltaBuffer), ...]``."""
    from repro.graph.deltas import EdgeDeltaBuffer

    rng = np.random.default_rng(seed)
    n = pg.graph.n_vertices
    part = pg.part_of_vertex
    muts = []
    for epoch in range(DYNAMIC_EPOCHS):
        m = int(round(rate * n))
        if m == 0:
            continue
        buf = EdgeDeltaBuffer()
        for v in rng.choice(n, size=m, replace=False):
            target = int(rng.integers(pg.n_parts))
            if target == int(part[v]):
                target = (target + 1) % pg.n_parts
            pool = np.flatnonzero(part == target)
            nbrs = rng.choice(
                pool, size=min(DYNAMIC_MIGRANT_EDGES, pool.size),
                replace=False,
            )
            for u in nbrs:
                buf.insert(int(v), int(u))
                buf.insert(int(u), int(v))
        muts.append((epoch + 1, buf))
    return muts


def _dynamic_steady_cost(pg) -> dict:
    """Steady-state elastic serving cost of ``pg``'s graph under ``pg``'s
    partition map: one BFS trace whose tau carries a wire term
    (``DYNAMIC_MSG_COST`` seconds per remote message) on top of the
    calibrated alpha/beta model, ffd-planned and billed at the fine
    ``DYNAMIC_DELTA`` quantum.  Remote messages are exactly what a stale map
    keeps paying for migrant edges, so this is the sweep's cost axis."""
    _, trace = run_sssp(pg, 0, collect_subgraphs=False)
    tau = (
        DEFAULT_ALPHA * trace.verts_processed
        + DEFAULT_BETA * trace.edges_examined
        + DYNAMIC_MSG_COST * trace.msgs_sent
    )
    tau = np.where(trace.active, tau, 0.0).astype(np.float64)
    cost = evaluate(
        ffd_placement(TimeFunction(tau)), BillingModel(delta=DYNAMIC_DELTA)
    )
    return {
        "elastic_cost_quanta": int(cost.cost_quanta),
        "makespan_s": float(cost.makespan),
        "remote_msgs": int(trace.msgs_sent.sum()),
    }


def _dynamic_run(pg, muts, *, repartition: bool):
    """One elastic run with mid-traversal delta merges; map frozen or
    incrementally repartitioned at every merge.  Dogfoods the session API
    end to end: ``open_session -> session.executor -> run(mutations=...)``.
    Returns ``(metrics_row, final_dist)``."""
    from repro.core.repartition import RepartitionConfig, partition_penalty
    from repro.graph import open_session

    session = open_session(pg, EngineConfig(window=DYNAMIC_WINDOW))
    _, trace0 = run_sssp(pg, 0, collect_subgraphs=False)
    tf0 = TimeFunction.from_trace(trace0)  # the pre-mutation prior
    ex = session.executor()
    rcfg = (
        RepartitionConfig(max_moves=DYNAMIC_MAX_MOVES, balance=DYNAMIC_BALANCE)
        if repartition
        else None
    )
    t0 = time.perf_counter()
    rep = ex.run(
        0,
        ffd_placement(tf0),
        strategy_fn=ffd_placement,
        replan=True,
        sketch=tf0,
        mutations=muts,
        repartition=rcfg,
    )
    wall = time.perf_counter() - t0
    assert rep.mutations_applied == len(muts), (
        f"dynamic: {rep.mutations_applied}/{len(muts)} delta buffers applied"
    )
    final = ex.pg
    row = {
        "penalty": int(partition_penalty(final.graph, final.part_of_vertex)),
        "supersteps": int(rep.n_supersteps),
        "mutations_applied": int(rep.mutations_applied),
        "repartition_moves": int(rep.repartition_moves),
        "run_cost_quanta": int(rep.cost.cost_quanta),
        "replans": int(rep.replans),
        "wall_s": float(wall),
    }
    row.update(_dynamic_steady_cost(final))
    return row, rep.dist


def _dynamic_sweep() -> dict:
    """Mutation-rate sweep, frozen vs repartitioned map per rate.  The
    staleness-vs-throughput acceptance bar is asserted in-run: at every
    nonzero rate the repartitioned map must strictly beat the frozen one on
    partition penalty and never lose on steady-state elastic cost, with a
    strict cost win at >= 1 rate -- while converging to identical
    distances."""
    g = rmat_graph(DYNAMIC_SCALE, DYNAMIC_DEGREE, seed=3)
    pg = bfs_grow_partition(g, DYNAMIC_PARTS, seed=1)
    per_rate = {}
    for i, rate in enumerate(DYNAMIC_RATES):
        muts = _dynamic_mutations(pg, rate, seed=100 + i)
        frozen, dist_f = _dynamic_run(pg, muts, repartition=False)
        repart, dist_r = _dynamic_run(pg, muts, repartition=True)
        assert np.array_equal(np.asarray(dist_f), np.asarray(dist_r)), (
            f"dynamic rate {rate}: repartitioning changed the fixpoint"
        )
        if rate > 0:
            assert repart["repartition_moves"] > 0, (
                f"dynamic rate {rate}: repartitioner moved nothing"
            )
            assert repart["penalty"] < frozen["penalty"], (
                f"dynamic rate {rate}: repartitioned penalty "
                f"{repart['penalty']} not below frozen {frozen['penalty']}"
            )
            assert (
                repart["elastic_cost_quanta"] <= frozen["elastic_cost_quanta"]
            ), (
                f"dynamic rate {rate}: repartitioned steady cost "
                f"{repart['elastic_cost_quanta']} above frozen "
                f"{frozen['elastic_cost_quanta']}"
            )
        else:
            assert repart["penalty"] == frozen["penalty"], (
                "dynamic rate 0: maps should be untouched"
            )
        per_rate[str(rate)] = {
            "mutation_epochs": len(muts),
            "inserted_edges": int(sum(len(b) for _, b in muts)),
            "frozen": frozen,
            "repartitioned": repart,
        }
    assert any(
        row["repartitioned"]["elastic_cost_quanta"]
        < row["frozen"]["elastic_cost_quanta"]
        for key, row in per_rate.items()
        if float(key) > 0
    ), "dynamic: no rate shows a strict elastic-cost win for repartitioning"
    return {
        "graph": {
            "n_vertices": g.n_vertices,
            "n_edges": g.n_edges,
            "n_parts": DYNAMIC_PARTS,
        },
        "epochs": DYNAMIC_EPOCHS,
        "migrant_edges": DYNAMIC_MIGRANT_EDGES,
        "msg_cost_s": DYNAMIC_MSG_COST,
        "billing_delta_s": DYNAMIC_DELTA,
        "per_rate": per_rate,
    }


def _print_dynamic_sweep(sweep: dict) -> None:
    for rate, row in sweep["per_rate"].items():
        fr, rp = row["frozen"], row["repartitioned"]
        print(
            f"dynamic rate {rate}: +{row['inserted_edges']} edges over "
            f"{row['mutation_epochs']} epochs, penalty {fr['penalty']} -> "
            f"{rp['penalty']} ({rp['repartition_moves']} moves), steady "
            f"cost {fr['elastic_cost_quanta']} -> "
            f"{rp['elastic_cost_quanta']} quanta, remote msgs "
            f"{fr['remote_msgs']} -> {rp['remote_msgs']}"
        )


def run_dynamic_only(verbose: bool = True) -> dict:
    """``--dynamic``: compute just the streaming-mutation sweep and merge it
    into an existing ``BENCH_traversal.json`` (fresh file if none)."""
    out = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            out = json.load(f)
    out["dynamic"] = _dynamic_sweep()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        _print_dynamic_sweep(out["dynamic"])
        print(f"-> {OUT_PATH}")
    return out


SERVE_SMOKE_SCALE, SERVE_SMOKE_DEGREE = 8, 4
SERVE_SMOKE_QUERIES = 40
SERVE_SMOKE_RATE = 10.0


def run_serve_smoke(verbose: bool = True) -> None:
    """``--serve-smoke``: serving CI gate (dense engine, in-process).

    On a tiny fixed-seed graph/trace: elastic throughput > 0, finite p99
    sojourn, elastic billed cost <= static, and deterministic replay (two
    ``run(trace)`` calls return equal reports, query records included).
    Never writes ``BENCH_traversal.json``.
    """
    import dataclasses

    from repro.graph.partition import hash_partition
    from repro.serve import ServiceConfig, TraversalService, poisson_trace

    g = rmat_graph(SERVE_SMOKE_SCALE, SERVE_SMOKE_DEGREE, seed=0)
    pg = hash_partition(g, SERVE_PARTS, seed=0)
    cfg = ServiceConfig(s_batch=4, window=8, tau_scale=SERVE_TAU_SCALE)
    trace = poisson_trace(
        SERVE_SMOKE_QUERIES, SERVE_SMOKE_RATE, g.n_vertices, seed=0
    )
    elastic = TraversalService(pg, config=cfg).run(trace)
    replay = TraversalService(pg, config=cfg).run(trace)
    assert elastic == replay, "serve smoke: replay not deterministic"
    static = TraversalService(
        pg, config=dataclasses.replace(cfg, static_vms=cfg.max_vms)
    ).run(trace)
    assert elastic.completed == SERVE_SMOKE_QUERIES, (
        f"serve smoke: {elastic.completed}/{SERVE_SMOKE_QUERIES} completed"
    )
    assert elastic.queries_per_sec > 0, "serve smoke: zero throughput"
    assert math.isfinite(elastic.sojourn_p99), "serve smoke: p99 not finite"
    assert elastic.cost.cost <= static.cost.cost, (
        f"serve smoke: elastic {elastic.cost.cost} > static {static.cost.cost}"
    )
    if verbose:
        print(
            f"serve smoke: {elastic.completed} queries at "
            f"{elastic.queries_per_sec:.1f} qps, p99 "
            f"{elastic.sojourn_p99:.3f}s, elastic {elastic.cost.cost_quanta} "
            f"<= static {static.cost.cost_quanta} quanta, replay "
            f"deterministic: True"
        )


# -- CI smoke: invariants on a tiny graph + committed-JSON schema check -------

SMOKE_SCALE, SMOKE_DEGREE, SMOKE_PARTS = 8, 4, 8
SMOKE_DEVICES = 4


def _smoke_child() -> dict:
    """Tiny-graph invariant pass under forced devices (seconds, not minutes):
    wire-savings, elastic-vs-static billing, and relayout bit-identity."""
    import jax

    from repro.dist.sharding import partition_mesh
    from repro.graph.traversal import get_engine

    assert len(jax.devices()) >= SMOKE_DEVICES
    g = rmat_graph(SMOKE_SCALE, SMOKE_DEGREE, seed=3)
    pg = bfs_grow_partition(g, SMOKE_PARTS, seed=1)

    # wire-savings invariant: per-destination aggregation shrinks the wire
    res = get_engine(
        pg, config=EngineConfig(m_max=128, mesh=partition_mesh(SMOKE_DEVICES)),
    ).run([0])
    wire, pre = int(res.wire_msgs.sum()), int(res.msgs_sent.sum())
    assert 0 < wire < pre, f"wire-savings violated: {wire} vs {pre}"

    # kernel-backend parity invariant: the Pallas relax path (interpret
    # mode) reproduces the XLA mesh run bit-for-bit on the tiny graph
    res_k = get_engine(
        pg,
        config=EngineConfig(
            m_max=128, mesh=partition_mesh(SMOKE_DEVICES), backend="pallas-interpret",
        ),
    ).run([0])
    assert np.array_equal(np.asarray(res_k.dist), np.asarray(res.dist)), (
        "pallas-interpret mesh dist diverged from xla"
    )
    assert np.array_equal(
        np.asarray(res_k.wire_msgs), np.asarray(res.wire_msgs)
    ), "pallas-interpret mesh wire counters diverged from xla"

    # hub-mirroring invariant: mirrored-vs-unmirrored parity on the tiny
    # power-law graph with strictly fewer slots on the wire
    from repro.graph.program import SsspProgram

    res_m = get_engine(
        pg,
        config=EngineConfig(
            m_max=128, mesh=partition_mesh(SMOKE_DEVICES), mirror_degree=2,
        ),
    ).run([0])
    _assert_mirror_parity("sssp", SsspProgram(), res, res_m, "smoke: ")
    wire_m = int(np.asarray(res_m.wire_msgs).sum())
    assert 0 < wire_m < wire, (
        f"smoke: mirroring must put strictly fewer slots on the wire "
        f"({wire_m} vs {wire})"
    )

    # elastic-vs-static billing invariant: consolidation never costs more
    _, trace = run_sssp(pg, 0)
    tf = TimeFunction.from_trace(trace)
    model = BillingModel()
    elastic = evaluate(ffd_placement(tf), model)
    static = evaluate(default_placement(tf), model)
    assert elastic.cost_quanta <= static.cost_quanta, (
        f"elastic {elastic.cost_quanta} > static {static.cost_quanta}"
    )

    # dynamic re-layout invariant: identical results + billed economics
    # (window=1 makes every superstep a boundary so swaps actually happen)
    relayout = _relayout_pair(pg, ffd_placement(tf), SMOKE_DEVICES, window=1)
    assert relayout["dynamic"]["relayouts"] > 0, (
        "smoke relayout pair never swapped a layout -- gate is vacuous"
    )

    # delta-merge invariant: merging an EdgeDeltaBuffer into the mesh layout
    # is byte-identical, field by field, to a from-scratch build of the
    # mutated graph; the bounded repartitioner then never worsens the
    # partition penalty or the steady-state elastic cost of the mutated map
    import dataclasses

    from repro.core.repartition import (
        RepartitionConfig,
        incremental_repartition,
    )
    from repro.graph.deltas import apply_delta_buffer, merged_mesh_layout
    from repro.graph.partition import contiguous_device_map, mesh_edge_layout
    from repro.graph.structs import MeshEdgeLayout

    dmap = contiguous_device_map(SMOKE_PARTS, SMOKE_DEVICES)
    old_layout = mesh_edge_layout(pg, dmap, SMOKE_DEVICES)
    buf = _dynamic_mutations(pg, 0.05, seed=4)[0][1]
    new_pg = apply_delta_buffer(pg, buf)
    merged = merged_mesh_layout(pg, new_pg, old_layout)
    # a second fresh apply bypasses the merged layout primed into new_pg's
    # cache, so ``scratch`` really is a from-scratch build
    scratch = mesh_edge_layout(apply_delta_buffer(pg, buf), dmap, SMOKE_DEVICES)
    for f in dataclasses.fields(MeshEdgeLayout):
        a, b = getattr(merged, f.name), getattr(scratch, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and np.array_equal(a, b), (
                f"smoke: merged layout field {f.name} != from-scratch build"
            )
        else:
            assert a == b, f"smoke: merged layout field {f.name} differs"
    rep = incremental_repartition(
        new_pg, config=RepartitionConfig(balance=1.25)
    )
    assert rep.moves > 0 and rep.penalty_after < rep.penalty_before, (
        f"smoke: repartition did not improve the migrant penalty "
        f"({rep.penalty_before} -> {rep.penalty_after}, {rep.moves} moves)"
    )
    cost_frozen = _dynamic_steady_cost(new_pg)
    cost_repart = _dynamic_steady_cost(rep.pg)
    assert (
        cost_repart["elastic_cost_quanta"] <= cost_frozen["elastic_cost_quanta"]
    ), (
        f"smoke: repartitioned steady cost {cost_repart} above frozen "
        f"{cost_frozen}"
    )

    return {
        "wire_total": wire,
        "pre_agg_total": pre,
        "elastic_cost_quanta": int(elastic.cost_quanta),
        "static_cost_quanta": int(static.cost_quanta),
        "relayout": relayout,
        "delta_merge_identical": True,
        "repart_penalty": [int(rep.penalty_before), int(rep.penalty_after)],
        "repart_cost_quanta": [
            int(cost_frozen["elastic_cost_quanta"]),
            int(cost_repart["elastic_cost_quanta"]),
        ],
    }


def check_bench_schema(path: str = OUT_PATH) -> dict:
    """The committed bench JSON parses and carries every tracked section."""
    with open(path) as f:
        data = json.load(f)
    missing = [s for s in REQUIRED_SECTIONS if s not in data]
    assert not missing, f"{path} is missing sections: {missing}"
    for d_n, row in data["mesh_sweep"]["per_d"].items():
        if int(d_n) > 1:
            assert row["wire_total"] < row["pre_agg_total"], d_n
    assert data["program_sweep"]["per_program"], "empty program sweep"
    assert data["relayout"]["per_d"], "empty relayout sweep"
    kp = data["kernel_path"]["per_program"]
    assert kp, "empty kernel-path sweep"
    for name, row in kp.items():
        assert row.get("parity_ok") is True, (
            f"kernel_path[{name}]: backend parity not recorded as OK"
        )
    ms = data["mirror_sweep"]
    assert ms["per_degree"], "empty mirror sweep"
    for t, rows in ms["per_degree"].items():
        for name, row in rows.items():
            assert row.get("parity"), (
                f"mirror_sweep[{t}][{name}]: parity not recorded"
            )
    assert ms["best"]["wire_reduction"] >= 0.25, (
        f"mirror_sweep best reduction {ms['best']} below the 25% bar"
    )
    sv = data["serving"]
    assert sv["per_rate"], "empty serving sweep"
    stretch = sv.get("p99_stretch_bar", SERVE_P99_STRETCH)
    assert any(
        row["elastic_cost_win"]
        and row["p99_ratio_elastic_vs_static"] <= stretch
        for row in sv["per_rate"].values()
    ), (
        "serving: no rate shows elastic beating static on cost/1k with p99 "
        f"within {stretch}x"
    )
    dy = data["dynamic"]
    assert dy["per_rate"], "empty dynamic sweep"
    strict_win = False
    for rate, row in dy["per_rate"].items():
        if float(rate) <= 0:
            continue
        fr, rp = row["frozen"], row["repartitioned"]
        assert rp["repartition_moves"] > 0, (
            f"dynamic[{rate}]: repartitioner moved nothing"
        )
        assert rp["penalty"] < fr["penalty"], (
            f"dynamic[{rate}]: repartitioned penalty {rp['penalty']} not "
            f"below frozen {fr['penalty']}"
        )
        assert rp["elastic_cost_quanta"] <= fr["elastic_cost_quanta"], (
            f"dynamic[{rate}]: repartitioned steady-state cost above frozen"
        )
        strict_win |= rp["elastic_cost_quanta"] < fr["elastic_cost_quanta"]
    assert strict_win, (
        "dynamic: no nonzero mutation rate shows a strict elastic-cost win"
    )
    return data


def run_smoke(verbose: bool = True) -> None:
    """``--smoke``: CI gate.  Asserts the bench invariants on a tiny graph
    (forced-device child) and schema-checks the committed JSON; never writes
    ``BENCH_traversal.json``."""
    from repro.testing.forced_devices import run_forced_devices

    data = check_bench_schema()
    child = json.loads(
        run_forced_devices(
            os.path.abspath(__file__),
            "--smoke-child",
            n_devices=SMOKE_DEVICES,
            timeout=900,
        )
    )
    if verbose:
        print(
            f"smoke: schema OK ({', '.join(REQUIRED_SECTIONS)} present in "
            f"{OUT_PATH}, {len(data['program_sweep']['per_program'])} "
            f"programs); tiny-graph invariants OK (wire "
            f"{child['wire_total']}/{child['pre_agg_total']}, elastic "
            f"{child['elastic_cost_quanta']} <= static "
            f"{child['static_cost_quanta']} quanta, relayout billing "
            f"identical: {child['relayout']['billing_identical']}, delta "
            f"merge == from-scratch: {child['delta_merge_identical']}, "
            f"repart penalty {child['repart_penalty'][0]} -> "
            f"{child['repart_penalty'][1]})"
        )


def _print_program_sweep(sweep: dict) -> None:
    for name, row in sweep["per_program"].items():
        print(
            f"program {name}: {row['supersteps']} supersteps "
            f"({row['supersteps_per_sec']:.0f}/s), active frac "
            f"{row['mean_active_fraction']:.2f}, wire saved "
            f"{row['wire_reduction']:.0%}, elastic {row['elastic_cost_quanta']}"
            f" vs static {row['static_cost_quanta']} core-min "
            f"({row['elastic_saving_vs_static']:.0%} saved)"
        )


def run_programs_only(verbose: bool = True) -> dict:
    """``--programs``: compute just the program sweep and merge it into an
    existing ``BENCH_traversal.json`` (fresh file if none)."""
    out = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            out = json.load(f)
    out["program_sweep"] = _program_sweep()
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        _print_program_sweep(out["program_sweep"])
        print(f"-> {OUT_PATH}")
    return out


def run(verbose: bool = True) -> dict:
    g = rmat_graph(SCALE, DEGREE, seed=3)
    pg = bfs_grow_partition(g, N_PARTS, seed=1)
    rng = np.random.default_rng(0)
    sources = rng.choice(g.n_vertices, size=N_SOURCES, replace=False).tolist()

    # warm both paths so the numbers compare steady-state orchestration,
    # then report compile (cold - warm) separately
    t0 = time.perf_counter()
    trace = run_bc_forward(pg, sources, max_supersteps=512)
    cold_batched = time.perf_counter() - t0
    t0 = time.perf_counter()
    trace = run_bc_forward(pg, sources, max_supersteps=512)
    warm_batched = time.perf_counter() - t0

    _serial_bc(pg, sources[:1])  # compile the superstep fn
    t0 = time.perf_counter()
    serial_steps, serial_syncs = _serial_bc(pg, sources)
    warm_serial = time.perf_counter() - t0

    total_steps = trace.n_supersteps
    total_edges = int(trace.edges_examined.sum())
    out = {
        "graph": {"n_vertices": g.n_vertices, "n_edges": g.n_edges, "n_parts": N_PARTS},
        "n_sources": N_SOURCES,
        "supersteps_total": int(total_steps),
        "serial_wall_s": warm_serial,
        "batched_wall_s": warm_batched,
        "batched_compile_s": max(0.0, cold_batched - warm_batched),
        "speedup_batched_vs_serial": warm_serial / warm_batched,
        "supersteps_per_sec": total_steps / warm_batched,
        "edges_examined_per_sec": total_edges / warm_batched,
        "host_syncs_serial": int(serial_syncs),
        "host_syncs_batched": 1,  # one bulk device_get per traversal batch
    }

    # windowed elastic executor: power-law (R-MAT) vs uniform (Erdos-Renyi)
    g_uni = erdos_renyi_graph(2**SCALE, float(DEGREE), seed=7)
    out["window_sweep"] = {
        "rmat": _window_sweep(pg),
        "uniform": _window_sweep(bfs_grow_partition(g_uni, N_PARTS, seed=1)),
    }

    # mesh-sharded engine device sweep (subprocess: needs forced devices)
    out["mesh_sweep"] = _mesh_sweep_subprocess()

    # VertexProgram sweep: algorithms x {dense rate, wire savings, elasticity}
    out["program_sweep"] = _program_sweep()

    # dynamic re-layout: static vs compute-follows-the-planner elastic runs
    out["relayout"] = _relayout_sweep_subprocess()

    # compute-backend sweep: xla vs pallas-interpret parity + TPU roofline
    out["kernel_path"] = _kernel_path_sweep()

    # hub mirroring: wire slots/bytes per superstep vs the unmirrored path
    out["mirror_sweep"] = _mirror_sweep_subprocess()

    # elastic serving: open-loop Poisson traces through TraversalService
    out["serving"] = _serving_sweep()

    # streaming mutations: frozen vs incrementally repartitioned maps
    out["dynamic"] = _dynamic_sweep()

    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        print(
            f"BC {N_SOURCES} sources on RMAT 2^{SCALE} (deg {DEGREE}, "
            f"{N_PARTS} parts): {total_steps} supersteps, "
            f"{serial_steps} serial-driver supersteps"
        )
        print(
            f"serial {warm_serial*1e3:.0f} ms ({serial_syncs} host syncs) vs "
            f"batched {warm_batched*1e3:.0f} ms (1 bulk transfer) -> "
            f"{out['speedup_batched_vs_serial']:.1f}x"
        )
        print(
            f"{out['supersteps_per_sec']:.0f} supersteps/s, "
            f"{out['edges_examined_per_sec']:.3g} edges/s -> {OUT_PATH}"
        )
        for shape, sw in out["window_sweep"].items():
            syncs = {k: v["host_syncs"] for k, v in sw["windows"].items()}
            print(
                f"window sweep [{shape}]: syncs per k {syncs}, "
                f"w8 vs w1 speedup {sw['speedup_w8_vs_w1']:.2f}x, "
                f"budget ok: {sw['sync_budget_w8_ok']}"
            )
        for d_n, row in out["mesh_sweep"]["per_d"].items():
            red = row["wire_reduction"]
            print(
                f"mesh sweep D={d_n}: wire {row['wire_total']} vs pre-agg "
                f"{row['pre_agg_total']} msgs over {row['supersteps']} "
                f"supersteps"
                + (f" ({red:.0%} saved by aggregation)" if red else "")
            )
        _print_program_sweep(out["program_sweep"])
        _print_relayout_sweep(out["relayout"])
        _print_kernel_path_sweep(out["kernel_path"])
        _print_mirror_sweep(out["mirror_sweep"])
        _print_serving_sweep(out["serving"])
        _print_dynamic_sweep(out["dynamic"])
    return out


if __name__ == "__main__":
    if "--mesh-child" in sys.argv:
        print(json.dumps(_mesh_child()))
    elif "--programs-child" in sys.argv:
        print(json.dumps(_programs_child()))
    elif "--relayout-child" in sys.argv:
        print(json.dumps(_relayout_child()))
    elif "--mirror-child" in sys.argv:
        print(json.dumps(_mirror_child()))
    elif "--smoke-child" in sys.argv:
        print(json.dumps(_smoke_child()))
    elif "--programs" in sys.argv:
        run_programs_only()
    elif "--relayout" in sys.argv:
        run_relayout_only()
    elif "--kernel-path" in sys.argv:
        run_kernel_path_only()
    elif "--mirror" in sys.argv:
        run_mirror_only()
    elif "--serving" in sys.argv:
        run_serving_only()
    elif "--dynamic" in sys.argv:
        run_dynamic_only()
    elif "--serve-smoke" in sys.argv:
        run_serve_smoke()
    elif "--smoke" in sys.argv:
        run_smoke()
    else:
        run()
