"""Elastic BSP executor: run a subgraph-centric job under a placement schedule
on a pool of jax devices standing in for cloud VMs.

The executed job is any ``graph.program.VertexProgram`` (``program=``):
non-stationary traversals (BFS/SSSP/WCC) whose active partition set sweeps
and dies out, or stationary algorithms (PageRank) that keep every partition
hot for a fixed budget -- the contrast the paper's placement strategies are
about.  The replanner's extrapolation defaults follow the program
(``ReplanConfig.for_program``): stationary workloads get a flat
activity-decay prior instead of the traversal decay fit.

The mapping from the paper's cloud model to JAX:

  VM slot j            -> a jax device (round-robin over the local pool)
  partition placement  -> the partition's state shard is kept device-resident
                          on its VM's device; when the schedule moves it, the
                          shard is ``jax.device_put`` to the target device and
                          the transfer (``partition_bytes / move_bandwidth``)
                          is billed into the receiving VM's busy time (pinned
                          strategies therefore never move state and pay no
                          migration seconds)
  superstep compute    -> the jitted global relaxation (mathematically equal
                          to per-VM sequential execution of its partitions;
                          per-VM time is accounted from the exact work
                          counters x the calibrated rate)
  billing              -> repro.core.billing on the *actual* executed trace

Windowed execution (the scaling knob): ``EngineConfig(window=k)`` executes
``k`` supersteps per device launch on the resumable ``TraversalEngine``
window API and pulls only the ``O(k*P)`` counter window at each placement
point -- one bulk host sync per window (``ceil(S/k) + 1`` syncs per run, the
+1 being the final distance pull) instead of a frontier/counter round-trip
every superstep.  ``window=1`` is the legacy per-superstep path,
bit-identical in ``dist`` and work counters for any ``k`` (the math does not
depend on where the window boundaries fall).

Mesh execution (``EngineConfig(mesh=partition_mesh(D))``): the traversal
itself runs on the mesh-sharded engine (partition axis -> device mesh, real
all-to-all exchange; see ``graph.mesh_exchange``), and the per-window
placement commit becomes *physical resharding*: each partition's state
shard is ``place_shard``-ed to the device its VM maps onto
(``Placement.device_row``), so migration is a device-to-device transfer, not
a bookkeeping entry.  Two ledgers are kept deliberately separate:

  * ``migration_bytes`` / ``CostReport.migration_secs`` bill the *simulated
    cloud* moves of the plan (every VM change, priced at
    ``move_bandwidth``); they are bit-identical for any device count -- the
    paper's economics must not depend on how many local devices stand in
    for the VMs.
  * ``device_moves`` / ``device_move_bytes`` count the bytes that *actually
    crossed jax devices*; with at least as many mesh devices as concurrently
    active VMs the VM -> device map is injective and the two ledgers
    coincide -- the billed migration is the physical one.

Dynamic re-layout (``EngineConfig(relayout=True)``, mesh mode) closes the
loop the data-plane resharding left open: the *compute* layout follows the
planner too.  At every window boundary the spliced placement row is bridged
onto mesh devices (``Placement.device_row`` via ``device_of_vm``) and handed
to ``TraversalEngine.run_window(device_of_part=...)`` -- the engine swaps to
the matching ``MeshEdgeLayout`` (incrementally rebuilt, LRU-cached consts
and jit) and remaps the carried state exactly, so ``dist``/counters stay
bit-identical to the static-layout run while each partition's local closure
genuinely executes on its planned device (``residency`` then records the
engine's active map).  The remap's bytes land in the *physical* ledger
(``device_moves``/``device_move_bytes``) -- real interconnect traffic -- and
deliberately NOT in ``migration_secs``: the billed cloud migration prices
the plan's VM moves only, so the paper's economics stay independent of how
many local devices stand in for the VMs, with or without re-layout.
Partitions the row leaves unplaced keep their previous compute device.

``residency`` records the per-window partition -> device map for inspection
(the ``--mesh`` demo prints it): the planned data-plane placement under
``relayout=False``, the engine's actual compute map under ``relayout=True``.

Beyond the paper: ``replan=True`` complements the static a-priori plan with
dynamic re-planning (their s7 future work) -- when the actually-active
partition set diverges from the prediction at a window boundary, the
remaining horizon is re-planned by ``repro.core.replan.OnlineReplanner``:
the observed tau prefix is extrapolated per-partition (geometric activity
decay + an activation floor) and the strategy re-runs over the full
remaining horizon, so one divergence costs one replan.  When a metagraph
``sketch`` TimeFunction is supplied, the decay rates and activation floor of
partitions with too-short observed histories are fitted from the sketch
instead of global defaults.  Replan knobs live on ``replan.ReplanConfig``
and can be passed via ``replan_config``.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.billing import BillingModel, CostReport, evaluate
from repro.core.placement import Placement, device_of_vm
from repro.core.repartition import RepartitionConfig, incremental_repartition
from repro.core.replan import OnlineReplanner, ReplanConfig
from repro.core.timing import DEFAULT_ALPHA, DEFAULT_BETA, TimeFunction
from repro.graph import deltas as graph_deltas
from repro.graph.config import EngineConfig, versioned_report
from repro.graph.mesh_exchange import place_shard
from repro.graph.program import SsspProgram, VertexProgram
from repro.graph.structs import PartitionedGraph
from repro.graph.traversal import get_engine


@dataclasses.dataclass
class ExecutionReport:
    dist: np.ndarray
    actual_tau: TimeFunction
    cost: CostReport
    n_supersteps: int
    n_migrations: int  # partition moves between devices
    migration_bytes: int  # total bytes of partition state moved
    replans: int
    host_syncs: int  # bulk device->host pulls (windows + final dist)
    window: int
    wall_seconds: float
    device_moves: int = 0  # shard moves that crossed real jax devices
    device_move_bytes: int = 0  # bytes physically transferred between devices
    residency: np.ndarray | None = None  # [n_windows, P] device per partition
    # (-1 = not yet placed), recorded at each window boundary
    relayouts: int = 0  # windows whose compute layout was actually swapped
    relayouts_skipped: int = 0  # proposed swaps vetoed by the "auto" policy
    # (projected move bytes exceeded the estimated remaining locality gain)
    mutations_applied: int = 0  # delta buffers merged at window boundaries
    repartition_moves: int = 0  # vertices migrated by the bounded LPA pass

    @property
    def migration_secs(self) -> float:
        """bytes / move_bandwidth, billed into the makespan (single source
        of truth: the cost report)."""
        return self.cost.migration_secs

    def asdict(self) -> dict:
        """Schema-versioned named-field view (``graph.config``); consumers
        key on names -- the dataclass field order is not a contract."""
        fields = {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
        }
        return versioned_report("execution_report", fields)


class ElasticBSPExecutor:
    """Executes any ``VertexProgram`` under a placement schedule with elastic
    devices (default program: weighted SSSP == BFS on unit weights)."""

    def __init__(
        self,
        pg: PartitionedGraph,
        *,
        program: VertexProgram | None = None,
        alpha: float = DEFAULT_ALPHA,
        beta: float = DEFAULT_BETA,
        tau_scale: float = 1.0,
        billing: BillingModel | None = None,
        config: EngineConfig | None = None,
    ):
        cfg = config or EngineConfig()
        self.config = cfg
        self.pg = pg
        self.program = program or SsspProgram()
        self.alpha = alpha
        self.beta = beta
        self.tau_scale = tau_scale
        self.billing = billing or BillingModel()
        self.mesh = cfg.mesh
        self.backend = cfg.backend
        self.mirror_degree = cfg.mirror_degree
        self.engine = get_engine(pg, program=self.program, config=cfg)
        self.devices = (
            list(cfg.mesh.devices.flat)
            if cfg.mesh is not None
            else jax.devices()
        )
        # per-partition index lists into the carried state's trailing axis
        # (identity layout on the dense engine, padded device-major positions
        # on the mesh engine) for shard gathers, and shard sizes in bytes
        # (per the program's state dtype) for migration pricing.  Dynamic
        # re-layout changes the state layout mid-run, so the index lists are
        # refreshed from the engine's active map (cached per layout key,
        # LRU-bounded like the engine's layout caches so a long replanned
        # relayout run cannot accrete index arrays per distinct placement).
        self._part_indices_cache: OrderedDict = OrderedDict()
        self._part_indices = self._state_part_indices()
        itemsize = np.dtype(self.program.dtype).itemsize
        nv, _ = pg.partition_sizes
        self.partition_bytes = (itemsize * nv).astype(np.int64)

    _PART_INDICES_CACHE_MAX = 8

    #: ``relayout="auto"`` break-even horizon: a proposed swap is committed
    #: only if the moved partitions' remaining planned-active supersteps
    #: (byte-weighted) cover at least this many windows' worth of the move
    AUTO_RELAYOUT_MIN_STEPS = 4

    def _state_part_indices(self) -> list:
        """Per-partition device-array indices into the carried state's
        trailing axis, for the engine's *active* layout (LRU per map)."""
        dop = self.engine.device_of_part
        key = None if dop is None else dop.tobytes()
        cached = self._part_indices_cache.get(key)
        if cached is None:
            state_idx = self.engine.state_index_of_vertex
            cached = [
                jnp.asarray(state_idx[np.flatnonzero(self.pg.part_of_vertex == i)])
                for i in range(self.pg.n_parts)
            ]
            self._part_indices_cache[key] = cached
        self._part_indices_cache.move_to_end(key)
        while len(self._part_indices_cache) > self._PART_INDICES_CACHE_MAX:
            self._part_indices_cache.popitem(last=False)
        return cached

    def _device_of_vm(self, j: int):
        return self.devices[device_of_vm(j, len(self.devices))]

    def _apply_mutation(self, buf, state, repartition, replanner):
        """Window-boundary delta merge: swap graph + engine, carry state.

        Returns ``(carried_state, repartition_moves)``.  Insert-only (a
        delete cannot be un-relaxed from in-flight monotone state) and
        monotone-only; the merged mesh layout is primed incrementally so the
        new engine adopts it instead of rebuilding, and a repartition pass
        re-primes the replanner's sketch from the fresh per-partition stats.
        """
        if buf.has_deletes:
            raise ValueError(
                "elastic mutations are insert-only: a delete cannot be "
                "un-relaxed from in-flight state"
            )
        if getattr(self.program, "stationary", False):
            raise ValueError(
                "mid-run mutations are monotone-programs-only "
                f"(got stationary {self.program.key})"
            )
        old_pg = self.pg
        old_engine = self.engine
        old_layout = (
            old_engine._mesh_prog.layout
            if old_engine._mesh_prog is not None
            else None
        )
        new_pg = graph_deltas.apply_delta_buffer(old_pg, buf)
        rep = None
        if repartition:
            rcfg = (
                repartition
                if isinstance(repartition, RepartitionConfig)
                else RepartitionConfig(mirror_degree=self.config.mirror_degree)
            )
            rep = incremental_repartition(new_pg, config=rcfg)
            new_pg = rep.pg
        if old_layout is not None and (rep is None or rep.moves == 0):
            graph_deltas.merged_mesh_layout(old_pg, new_pg, old_layout)
        self.pg = new_pg
        self.engine = get_engine(new_pg, program=self.program, config=self.config)
        self._part_indices_cache = OrderedDict()
        self._part_indices = self._state_part_indices()
        itemsize = np.dtype(self.program.dtype).itemsize
        nv, _ = new_pg.partition_sizes
        self.partition_bytes = (itemsize * nv).astype(np.int64)
        new_layout = (
            self.engine._mesh_prog.layout
            if self.engine._mesh_prog is not None
            else None
        )
        identity = self.program.identity
        state = graph_deltas.carry_state(
            old_layout, new_layout, state, identity=identity, mesh=self.mesh
        )
        isrc, _, _ = buf.inserts()
        if isrc.size:
            state = graph_deltas.reactivate_sources(
                state, new_layout, isrc, identity=identity
            )
        if rep is not None:
            replanner.reprime(rep.part_activity)
        return state, (rep.moves if rep is not None else 0)

    def run(
        self,
        source: int,
        plan: Placement,
        *,
        strategy_fn: Callable[[TimeFunction], Placement] | None = None,
        replan: bool = False,
        replan_config: ReplanConfig | None = None,
        sketch: TimeFunction | None = None,
        max_supersteps: int = 4096,
        mutations=None,
        repartition: RepartitionConfig | bool | None = None,
    ) -> ExecutionReport:
        """Execute the program under ``plan``; see the module docstring.

        The window depth and the re-layout policy are the executor's
        ``config.window`` and ``config.relayout``.

        ``relayout=True`` (mesh mode; a no-op dense, where one device does
        all the work) makes the compute layout follow the planner: each
        window's spliced placement row is applied as a
        ``device_of_part`` override so partitions compute on their planned
        devices, with remap bytes billed to the physical
        ``device_moves``/``device_move_bytes`` ledger and results
        bit-identical to the static-layout run.

        ``relayout="auto"`` is the cost-aware variant: each proposed swap's
        projected ``device_move_bytes`` (the physical ledger's own pricing
        of the moved partitions) is weighed against the estimated locality
        gain over the remaining horizon -- the moved partitions'
        byte-weighted count of remaining planned-active supersteps in
        ``vm_of``.  Swaps whose payback horizon falls under
        ``AUTO_RELAYOUT_MIN_STEPS`` are skipped (counted in
        ``ExecutionReport.relayouts_skipped``); committed swaps behave
        exactly like ``relayout=True``.  Results stay bit-identical either
        way -- the policy only changes *where* partitions compute.
        """
        pg = self.pg
        t0 = time.perf_counter()
        window = max(1, int(self.config.window))
        relayout = self.config.relayout
        auto_relayout = isinstance(relayout, str) and relayout == "auto"
        relayout = (
            (auto_relayout or bool(relayout))
            and self.engine.device_of_part is not None
        )
        muts = sorted(mutations or (), key=lambda tb: int(tb[0]))
        mut_idx = 0
        mutations_applied = 0
        repartition_moves = 0

        state = self.engine.init_state([source])
        replanner = OnlineReplanner(
            pg.n_parts, strategy_fn,
            replan_config or ReplanConfig.for_program(self.program),
            sketch=sketch,
        )

        vm_of = plan.vm_of.copy()
        horizon = vm_of.shape[0]
        n_dev = len(self.devices)
        prev_vm = np.full(pg.n_parts, -1, dtype=np.int64)
        prev_dev = np.full(pg.n_parts, -1, dtype=np.int64)  # real device slot
        shards: dict[int, jax.Array] = {}  # partition -> device-resident state
        migrations = 0
        migration_bytes = 0
        device_moves = 0
        device_move_bytes = 0
        mig_events: list[tuple[int, int, float]] = []  # (superstep, vm, secs)
        replans = 0
        relayouts = 0
        relayouts_skipped = 0
        host_syncs = 0
        taus: list[np.ndarray] = []
        vm_rows: list[np.ndarray] = []
        residency: list[np.ndarray] = []

        s = 0
        # superstep 0's active set is program-defined and host-known (the
        # source's partition for traversals, every partition for source-free
        # programs), so the first placement decision costs no device round-trip
        active_next = self.program.initial_active_parts(pg, [source])
        done = False

        while not done and s < max_supersteps:
            # -- window-boundary mutations: merge due delta buffers ----------
            # (the traversal hot path never sees the buffer -- the merge swaps
            # graph + engine between launches and carries the state exactly)
            while mut_idx < len(muts) and int(muts[mut_idx][0]) <= s:
                state, moved = self._apply_mutation(
                    muts[mut_idx][1], state, repartition, replanner
                )
                mut_idx += 1
                mutations_applied += 1
                repartition_moves += moved

            # -- placement point: (re-)plan, then commit to a whole window ---
            if s >= horizon or (
                replan and bool((active_next & (vm_of[s] < 0)).any())
            ):
                # prediction diverged (or ran past the plan): re-plan the
                # entire remaining horizon from the observed prefix
                vm_of = replanner.replan(vm_of, s, active_next)
                # pad to a window multiple (repeat the last planned row, which
                # places every partition thanks to the activation floor) so
                # replans never create remainder-sized window launches
                rem = (vm_of.shape[0] - s) % window
                if rem:
                    vm_of = np.vstack(
                        [vm_of, np.tile(vm_of[-1], (window - rem, 1))]
                    )
                replans += 1
                horizon = vm_of.shape[0]

            # never run past the plan: divergence inside a window is caught
            # at the next boundary, but an unplanned superstep never executes.
            # (each distinct k compiles the window program once per engine --
            # replanned horizons are padded to window multiples above, so the
            # only remainder launch is a plan's final partial window)
            k = max(1, min(window, horizon - s, max_supersteps - s))
            rows = vm_of[s : s + k]

            # -- dynamic re-layout: compute follows the plan -----------------
            # the window's boundary row decides where placed partitions
            # compute; unplaced ones keep their current device.  The remap is
            # real interconnect traffic -> the physical ledger; the billed
            # cloud migration (migration_secs) stays plan-derived below.
            target_map = None
            if relayout:
                cur = self.engine.device_of_part
                target_map = cur.copy()
                placed = rows[0] >= 0
                target_map[placed] = device_of_vm(rows[0][placed], n_dev)
                if np.array_equal(target_map, cur):
                    target_map = None
                else:
                    moved = np.flatnonzero(target_map != cur)
                    move_bytes = int(self.partition_bytes[moved].sum())
                    if auto_relayout:
                        # payback test: bytes moved now must be covered by
                        # the moved partitions' remaining planned activity
                        # (each future planned-active superstep of a moved
                        # partition benefits from the better locality, so
                        # weight it by the partition's shard bytes)
                        future_steps = (vm_of[s:, moved] >= 0).sum(axis=0)
                        gain = int(
                            (self.partition_bytes[moved] * future_steps).sum()
                        )
                        if move_bytes * self.AUTO_RELAYOUT_MIN_STEPS > gain:
                            target_map = None
                            relayouts_skipped += 1
                    if target_map is not None:
                        relayouts += 1
                        device_moves += int(moved.size)
                        device_move_bytes += move_bytes

            # -- one device launch, one bulk counter pull --------------------
            wres = self.engine.run_window(state, k, device_of_part=target_map)
            if target_map is not None:
                self._part_indices = self._state_part_indices()
            host_syncs += 1
            state = wres.state
            steps = int(wres.n_supersteps[0]) - s

            # -- stage the executed supersteps' scheduled movement -----------
            # only supersteps that actually ran move state: a window tail past
            # convergence never migrates, so counted moves == billed moves.
            # The VM move is the *billed* (simulated cloud) migration; the
            # place_shard below is the *physical* resharding -- partition i's
            # state genuinely moves to the device its VM maps onto
            # (Placement.device_row), and bytes that actually crossed jax
            # devices are tallied separately.
            for t in range(steps):
                row = rows[t]
                for i in np.flatnonzero(row >= 0):
                    j = int(row[i])
                    if prev_vm[i] == j:
                        continue
                    # the shard's placed result is retained for the whole
                    # run: partition i's state lives on its VM's device (the
                    # engine remains the compute source of truth -- this dict
                    # is the elastic data plane whose content refreshes at
                    # each move)
                    shards[i], crossed = place_shard(
                        state.dist[0, self._part_indices[i]],
                        self._device_of_vm(j),
                        self.devices[prev_dev[i]] if prev_dev[i] >= 0 else None,
                    )
                    if crossed:
                        device_moves += 1
                        device_move_bytes += int(self.partition_bytes[i])
                    if prev_vm[i] >= 0:
                        migrations += 1
                        migration_bytes += int(self.partition_bytes[i])
                        mig_events.append(
                            (
                                s + t,
                                j,
                                self.partition_bytes[i] / self.billing.move_bandwidth,
                            )
                        )
                    prev_vm[i] = j
                    prev_dev[i] = device_of_vm(j, n_dev)

            for t in range(steps):
                verts = wres.verts_processed[0, t].astype(np.float64)
                edges = wres.edges_examined[0, t].astype(np.float64)
                active_mask = verts > 0
                tau_row = self.tau_scale * (self.alpha * verts + self.beta * edges)
                tau_row = np.where(active_mask, tau_row, 0.0)
                taus.append(tau_row)
                vm_rows.append(np.where(active_mask, rows[t], -1))
                replanner.observe(tau_row)
            s += steps
            active_next = wres.part_active_next[0]
            done = bool(wres.done[0])
            # residency: planned data-plane devices (static layout) or the
            # engine's actual compute map (dynamic re-layout)
            residency.append(
                self.engine.device_of_part.astype(np.int64)
                if relayout
                else prev_dev.copy()
            )

        # the final bulk pull; mesh state comes back in padded device-major
        # order and is gathered to global vertex order host-side
        dist = self.engine.gather_global(np.asarray(state.dist))[0]
        host_syncs += 1

        tau = np.vstack(taus) if taus else np.zeros((0, pg.n_parts))
        actual_tf = TimeFunction(tau)
        executed = Placement(
            strategy=plan.strategy + ("+replan" if replans else ""),
            tau=tau,
            vm_of=np.vstack(vm_rows) if vm_rows else np.zeros((0, pg.n_parts), np.int64),
            always_on=plan.always_on,
            pinned=plan.pinned,
        )
        mig_busy = None
        if mig_events:
            j_max = max(j for _, j, _ in mig_events) + 1
            mig_busy = np.zeros((s, j_max))
            for step, j, secs in mig_events:
                mig_busy[step, j] += secs
        cost = evaluate(executed, self.billing, migration_busy=mig_busy)
        return ExecutionReport(
            dist=dist,
            actual_tau=actual_tf,
            cost=cost,
            n_supersteps=s,
            n_migrations=migrations,
            migration_bytes=migration_bytes,
            replans=replans,
            host_syncs=host_syncs,
            window=window,
            wall_seconds=time.perf_counter() - t0,
            device_moves=device_moves,
            device_move_bytes=device_move_bytes,
            residency=(
                np.stack(residency)
                if residency
                else np.zeros((0, pg.n_parts), dtype=np.int64)
            ),
            relayouts=relayouts,
            relayouts_skipped=relayouts_skipped,
            mutations_applied=mutations_applied,
            repartition_moves=repartition_moves,
        )
