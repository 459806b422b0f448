"""End-to-end elastic graph processing driver (the paper's system, running).

For each paper workload: plan placement from the metagraph *prediction*
(launch-time planning, no profiling run), execute the chosen vertex program
under that plan on the elastic executor (partition state device-resident per
schedule, migration bytes billed), bill the actual execution, and compare
against the default placement and the trace-oracle plan.  Also demonstrates
dynamic re-planning (paper s7 future work) when the prediction diverges.

Knobs:
  --algorithm A  which ``graph.program`` VertexProgram to execute:
               ``bfs`` (default, hop counts), ``sssp`` (weighted edges),
               ``wcc`` (min label propagation), or ``pagerank`` (stationary,
               fixed budget).  The metagraph prediction is BFS-shaped, so
               non-BFS runs show the replanner correcting a genuinely wrong
               prior -- and ``pagerank``'s flat all-partitions-active profile
               is the contrast case where elasticity has nothing to harvest.
  --window K   supersteps per device launch (the windowed executor pulls one
               O(K*P) counter window per placement point -- ceil(S/K)+1 host
               syncs per run; K=1 is the legacy per-superstep path)
  --no-replan  disable online re-planning; with it on, a divergence replans
               the full remaining horizon via activity-decay extrapolation
               (repro.core.replan, one replan per divergence; the metagraph
               prediction doubles as the replanner's sketch prior)
  --mesh N     run the mesh-sharded engine on N devices.  Under
               ``JAX_PLATFORMS=cpu`` the N devices are forced host devices
               (set before jax initializes -- this flag is pre-parsed);
               otherwise they are the real accelerators.  The engine shards
               the partition axis over the mesh, with a real all-to-all
               exchange and per-window *physical* shard migration.  Prints per-device
               shard residency at every window so the movement is visible.
  --relayout   (with --mesh) dynamic re-layout: at every window boundary the
               engine swaps its ``MeshEdgeLayout`` to the spliced placement
               row, so partitions *compute* on their planned devices (not
               just store their shards there).  Results are bit-identical;
               the remap bytes show up in the physical device-move ledger
               while billed migration stays plan-derived.  ``--relayout
               auto`` runs the cost-aware policy instead: a proposed swap is
               committed only when the projected wire savings over the
               remaining horizon pay for the shard-move bytes, and vetoed
               proposals are counted in ``relayouts_skipped``.
  --mirror-degree T
               (with --mesh) hub-vertex mirroring: vertices whose remote
               in-degree across wire blocks is >= T get a per-device mirror
               slot; remote edges into them combine locally and sync one
               value per (device, hub) per superstep, cutting wire slots on
               power-law graphs.  Results stay bit-identical for the
               min-programs (counters-exact for pagerank).  Omit for the
               unmirrored wire path.
  --backend B  compute backend for the superstep hot path: ``xla`` (default,
               segment reductions), ``pallas`` (block-skipping Pallas relax
               kernels -- needs a real accelerator), or ``pallas-interpret``
               (same kernels through the Pallas interpreter; runs anywhere,
               for parity checking, not speed).  Counters and collectives
               stay on XLA, so every backend reports bit-identical counters;
               min-programs also produce bit-identical state.

  PYTHONPATH=src python examples/elastic_bfs.py [--workloads LIVJ/8P ...]

Writing a new VertexProgram
---------------------------
The engine executes any member of the ``graph.program`` algebra; a new
algorithm is one small class away from windowed, mesh-sharded, elastically
placed execution.  Subclass ``VertexProgram`` and define:

  * ``reduce`` ("min" or "sum") -- the combine op every aggregation point
    (segment reductions, pre-all-to-all wire slots, receive scatter) routes
    through, with ``identity`` derived from it and ``dtype``;
  * ``relax(msg, w)`` -- the per-edge transform of the source state along an
    edge carrying plane value ``w`` (optionally override ``edge_plane`` +
    ``plane_key`` to replace the graph weights, as PageRank does with
    ``1/out_degree[src]``);
  * ``init(pg, sources)`` -- initial ``(state, frontier)`` in vertex order;
  * monotone programs inherit the closure shape and the ``is_active``
    frontier predicate (``new < old``); stationary programs set
    ``stationary=True`` and provide ``apply(state, acc, n)`` plus a
    ``superstep_budget``.

Then hand an instance to ``--algorithm``'s registry, ``get_engine(pg,
program=...)``, or ``ElasticBSPExecutor(pg, program=...)``; dense/mesh
equivalence, ``[S, k, P]`` counters, and migration billing come for free.
"""

import argparse
import os
import sys


def _preparse_mesh() -> int:
    """Read --mesh N from argv before anything imports jax."""
    for i, a in enumerate(sys.argv):
        if a == "--mesh" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--mesh="):
            return int(a.split("=", 1)[1])
    return 0


_MESH = _preparse_mesh()
_FORCE_HOST = _MESH > 1 and os.environ.get("JAX_PLATFORMS") == "cpu"
if _FORCE_HOST:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_MESH}"
    ).strip()

from repro.core import BillingModel, evaluate, default_placement, lap_placement, ffd_placement
from repro.core.elastic import ElasticBSPExecutor
from repro.core.metagraph import predict_time_function
from repro.data import paper_workloads
from repro.graph.config import EngineConfig


def bc_demo(wl, n_sources: int, strat, model):
    """Multi-wave BC on the batched device-resident engine: generate the
    whole wave trace in one traversal, then price the elasticity between
    waves (the paper's s7 'sinusoidal' activation)."""
    from repro.core.timing import TimeFunction
    from repro.graph.bsp import run_bc_forward

    sources = [(i * 997) % wl.pg.graph.n_vertices for i in range(n_sources)]
    trace = run_bc_forward(wl.pg, sources)
    tf = TimeFunction.from_trace(trace).scaled_to_tmin(wl.tf.t_min() * n_sources)
    r = evaluate(strat(tf), model)
    r_def = evaluate(default_placement(tf), model)
    print(
        f"BC {n_sources} waves ({trace.n_supersteps} supersteps, one batched "
        f"traversal): elastic {r.cost_quanta} vs default {r_def.cost_quanta} "
        f"core-min ({1 - r.cost_quanta / r_def.cost_quanta:.0%} saved)"
    )


def _print_residency(rep, n_devices: int):
    """Per-window partition -> device residency (the real migration)."""
    res = rep.residency
    if res is None or not len(res):
        return
    for w, row in enumerate(res):
        cells = " ".join(
            f"P{i}@d{int(d)}" if d >= 0 else f"P{i}@--"
            for i, d in enumerate(row)
        )
        moved = ""
        if w > 0:
            prev = res[w - 1]
            n_moved = int(((row != prev) & (prev >= 0) & (row >= 0)).sum())
            if n_moved:
                moved = f"   <- {n_moved} shard(s) moved devices"
        print(f"  window {w:2d}: {cells}{moved}")
    print(
        f"  physical: {rep.device_moves} device-to-device moves, "
        f"{rep.device_move_bytes} B crossed the {n_devices}-device mesh "
        f"(billed cloud moves: {rep.n_migrations} / {rep.migration_bytes} B)"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", nargs="*", default=["LIVJ/8P", "USRN/8P"])
    ap.add_argument("--strategy", default="lap", choices=["ffd", "lap"])
    ap.add_argument(
        "--algorithm", default="bfs",
        choices=["bfs", "sssp", "wcc", "pagerank"],
        help="VertexProgram to execute (see module docstring)",
    )
    ap.add_argument(
        "--window", type=int, default=8, metavar="K",
        help="supersteps per device launch (1 = legacy per-superstep sync)",
    )
    ap.add_argument(
        "--no-replan", action="store_true",
        help="disable online re-planning on prediction divergence",
    )
    ap.add_argument(
        "--mesh", type=int, default=0, metavar="N",
        help="run the mesh-sharded engine on N devices (forced host devices "
        "under JAX_PLATFORMS=cpu) with physical per-window shard migration",
    )
    ap.add_argument(
        "--relayout", nargs="?", const=True, default=False,
        choices=[True, "auto"], metavar="auto",
        help="(with --mesh) dynamic re-layout: the compute layout follows "
        "the planner at every window boundary -- partitions genuinely run "
        "on their planned devices, results stay bit-identical, and the "
        "residency print shows the planned map instead of the data plane; "
        "pass 'auto' for the cost-aware policy that vetoes swaps whose "
        "move bytes are not paid back by the remaining horizon",
    )
    ap.add_argument(
        "--mirror-degree", type=int, default=None, metavar="T",
        help="(with --mesh) mirror hub vertices with cross-partition "
        "in-degree >= T: remote edges into them combine locally and sync "
        "one value per (device, hub), cutting wire slots on power-law "
        "graphs with bit-identical min-program results",
    )
    ap.add_argument(
        "--backend", default="xla",
        choices=["xla", "pallas", "pallas-interpret"],
        help="superstep compute backend (see module docstring); "
        "pallas-interpret runs the kernels anywhere for parity checking",
    )
    ap.add_argument(
        "--bc", type=int, default=0, metavar="N",
        help="also run an N-source BC wave demo on the batched engine",
    )
    args = ap.parse_args()

    strat = {"ffd": ffd_placement, "lap": lap_placement}[args.strategy]
    model = BillingModel(delta=60.0)
    from repro.graph.program import BUILTIN_PROGRAMS

    program = BUILTIN_PROGRAMS[args.algorithm]()
    mesh = None
    if args.mesh > 1:
        from repro.dist.sharding import partition_mesh

        import jax

        mesh = partition_mesh(args.mesh)
        kind = (
            "forced host devices"
            if _FORCE_HOST
            else f"{jax.devices()[0].platform} devices "
            f"(of {len(jax.devices())} present)"
        )
        print(f"mesh: {args.mesh} {kind}, partition axis sharded")

    for wl in paper_workloads(tuple(args.workloads)):
        print(f"\n=== {wl.name} [{args.algorithm}] " + "=" * 40)
        # 1. a-priori plan from the metagraph (scaled to the same calibration).
        # The prediction models a BFS sweep; for other programs it is a
        # deliberately imperfect prior the replanner gets to correct.
        pred_tf, sched = predict_time_function(wl.pg, wl.source)
        pred_tf = pred_tf.scaled_to_tmin(wl.tf.t_min())
        plan = strat(pred_tf)
        print(
            f"planned {plan.n_vms} VMs over {pred_tf.n_supersteps} predicted "
            f"supersteps from {wl.pg.n_subgraphs} metagraph vertices"
        )

        # 2. execute under the plan with dynamic re-planning enabled; the
        # metagraph prediction doubles as the replanner's sketch prior
        from repro.core.timing import TimeFunction

        tau_scale = wl.tf.t_min() / max(
            1e-12, TimeFunction.from_trace(wl.trace).t_min()
        )
        # one EngineConfig carries every engine knob through the stack
        cfg = EngineConfig(
            mesh=mesh, backend=args.backend,
            mirror_degree=args.mirror_degree,
            window=args.window, relayout=args.relayout,
        )
        ex = ElasticBSPExecutor(
            wl.pg, program=program, tau_scale=tau_scale, billing=model,
            config=cfg,
        )
        rep = ex.run(
            wl.source, plan, strategy_fn=strat, replan=not args.no_replan,
            sketch=None if args.no_replan else pred_tf,
        )
        print(
            f"executed {rep.n_supersteps} supersteps in windows of "
            f"{rep.window} ({rep.host_syncs} host syncs, {rep.replans} "
            f"replans, {rep.n_migrations} migrations moving "
            f"{rep.migration_bytes} B, {rep.relayouts} compute re-layouts"
            + (
                f" ({rep.relayouts_skipped} vetoed by the payback policy)"
                if rep.relayouts_skipped else ""
            )
            + f", wall {rep.wall_seconds:.1f}s on this host)"
        )
        if mesh is not None:
            _print_residency(rep, args.mesh)
        print(
            f"actual billing: {rep.cost.cost_quanta} core-min, makespan "
            f"{rep.cost.makespan:.1f}s = {rep.cost.makespan_over_tmin:.2f}x "
            f"T_Min (migration {rep.migration_secs:.2f}s billed in)"
        )

        # 3. compare against default and the trace-oracle plan.  The
        # workload's recorded trace is a run of the engine's *default*
        # program (weighted SSSP -- plain BFS on unweighted graphs, but e.g.
        # ORKT/40P is deliberately weighted), so it is only a fair oracle
        # when the executed algorithm is that same program; every other
        # combination is judged against its own executed tau.
        trace_matches = args.algorithm == "sssp" or (
            args.algorithm == "bfs" and wl.pg.graph.weights is None
        )
        oracle_tf = wl.tf if trace_matches else rep.actual_tau
        r_def = evaluate(default_placement(oracle_tf), model)
        r_oracle = evaluate(strat(oracle_tf), model)
        save = 1 - rep.cost.cost_quanta / r_def.cost_quanta
        print(
            f"default: {r_def.cost_quanta} core-min | trace-oracle "
            f"{args.strategy}: {r_oracle.cost_quanta} core-min | "
            f"metagraph-planned: {rep.cost.cost_quanta} core-min "
            f"({save:.0%} saved vs default)"
        )

        if args.bc:
            bc_demo(wl, args.bc, strat, model)


if __name__ == "__main__":
    main()
