"""Pure-JAX subgraph-centric BSP engines, parameterized by a VertexProgram.

Semantics follow GoFFish (paper s3.1): within a BSP superstep, every *active*
subgraph runs its local traversal to closure over **local** edges (a
``jax.lax.while_loop`` of frontier-masked edge relaxations); at the superstep
boundary, remote edges deliver messages, and vertices improved by a remote
message form the next superstep's frontier (their subgraphs become active).
The engine also accumulates the per-partition *work counters* (vertices
processed, edges examined) that instantiate the paper's time function A.
Every arc counts toward its source's partition, so the dense window takes
them from per-vertex degrees, reduced densely into parts, not per-arc scatters.

The per-edge/per-vertex math is no longer hard-coded BFS: both window
programs route every relaxation, segment reduction, frontier predicate, and
state-init through a ``graph.program.VertexProgram`` (default:
``SsspProgram``, whose traced ops are exactly the old engine's -- BFS on
unit-weight graphs stays bit-identical).  Monotone programs (BFS, weighted
SSSP, WCC) keep the local-closure-then-exchange shape; stationary programs
(PageRank) run one local gather pass per superstep, fold the accumulated
messages with ``program.apply`` at the boundary, and drain the frontier when
the iteration budget is exhausted -- same windowing, counters, and elastic
seams either way.

Execution modes sharing the same math:

  * ``make_superstep_fn`` -- one jitted superstep, host loop outside (legacy
    per-superstep orchestration, kept as the equivalence oracle).
  * ``TraversalEngine`` (dense) -- the device-resident engine: the *entire*
    traversal (inner local-closure loop, remote exchange, work-counter
    accumulation) is a single jitted ``lax.while_loop`` writing per-superstep
    counters into preallocated ``[S, m_max, P]`` device buffers, one bulk
    transfer after convergence.  State carries a leading source axis ``S``
    so multi-source sweeps (BC forward) amortize compilation and launches.
  * ``TraversalEngine(config=EngineConfig(mesh=...))`` -- the
    **mesh-sharded** engine: the same window program, but the partition axis
    is laid out over a 1-D ``jax.sharding.Mesh``
    (``dist.sharding.partition_mesh``).  Each device owns a fixed-shape
    padded vertex shard (``structs.MeshEdgeLayout``), the local closure runs
    per device with ``pmax``-synchronized iteration counts, and the
    superstep-boundary exchange is a *real* collective: per-destination
    min-aggregation into static wire slots (one message per ``(dst_vertex,
    dst_device)``, not per edge) followed by one static-shape
    ``jax.lax.all_to_all`` (``graph.mesh_exchange``).  Distances and the
    ``[S, m_max, P]`` counters are bit-identical to the dense engine for any
    device count; a one-device mesh silently uses the dense path.

Exchange contract (mesh mode): the carried state is the padded device-major
layout ``[S, n_devices * n_pad]`` sharded on the trailing axis --
``state_index_of_vertex`` maps vertex ids into it and ``gather_global`` maps
results back; ``run``/``run_window`` signatures are unchanged and
host-visible results are always in global vertex order.  The extra
``wire_msgs`` counter records post-aggregation messages put on the collective
per superstep (0 on the dense path, where nothing crosses a wire).

Single-device-only paths: ``collect_subgraphs`` (metagraph ground-truth
bitmasks) and ``make_superstep_fn`` do not have mesh twins; the engine
raises if both ``mesh`` and ``collect_subgraphs`` are requested.

All modes consume the static dst-sorted CSR layout built once per graph
(``partition.partitioned_edge_layout``, extended per device map by
``partition.mesh_edge_layout``): every segment reduction takes the
``indices_are_sorted`` fast path and no per-call ``argsort`` exists anywhere
on the traversal hot path.

Knobs (fields of ``graph.config.EngineConfig``, the engine's ``config``):
  * ``m_max``      -- trace-buffer depth = superstep cap.  Buffers are
    ``[S, m_max, P]`` int32; 4096 x 40 partitions is ~0.7 MB per counter.
  * batching ``S`` -- callers pass ``[S, n]`` initial state; one compiled
    ``while_loop`` serves any S (recompiles per distinct S).
  * ``collect_subgraphs`` -- also record per-superstep active-subgraph
    bitmasks ``[S, m_max, n_subgraphs]`` on device (the metagraph layer's
    ground truth), still transferred in the same single bulk pull.
  * ``mesh`` -- shard the partition axis over mesh devices; the engine's
    own ``device_of_part`` argument places partitions on them (default:
    balanced contiguous blocks).

Windowed execution (``init_state`` / ``run_window``): the same device program
also runs *resumably* -- ``run_window(state, k)`` executes up to ``k``
supersteps in one launch, pulls only the ``[S, k, P]`` counter window (plus
the ``[S, P]`` next-active partition mask and done flags -- one bulk
``device_get`` per window), and leaves the carried dist/frontier state on
device (sharded across the mesh in mesh mode).  The elastic executor
interleaves placement decisions -- and, on a mesh, physical shard migration
-- at window boundaries; ``run`` is the degenerate single window of depth
``m_max``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.config import EngineConfig
from repro.graph.partition import partitioned_edge_layout
from repro.graph.program import (
    SsspProgram,
    VertexProgram,
    resolve_edge_plane,
    validate_program,
)
from repro.graph.structs import BoundedCache, PartitionedGraph
from repro.kernels.bfs_relax.ops import make_relax_fn, validate_backend
from repro.spans import FINISH, scope, span

#: per-graph bound on cached per-program edge-plane device arrays (keyed by
#: coerced ``plane_key``) and on cached engines (keyed by coerced knobs) --
#: the AL02 cache discipline: bounded LRU, canonical keys
_PLANE_CACHE_MAX = 8
_ENGINE_CACHE_MAX = 8


class SuperstepResult(NamedTuple):
    dist: jax.Array  # [n] float32, updated distances
    next_frontier: jax.Array  # [n] bool, vertices improved by remote messages
    edges_examined: jax.Array  # [P] int32, local edges scanned this superstep
    verts_processed: jax.Array  # [P] int32, frontier vertices processed
    msgs_sent: jax.Array  # [P] int32, remote messages emitted per src partition
    inner_iters: jax.Array  # [] int32, local-closure iterations


class _DeviceArrays(NamedTuple):
    """Device copies of the static per-graph arrays, uploaded once per graph
    and shared by every engine / superstep fn built on it."""

    lsrc: jax.Array
    ldst: jax.Array
    lw: jax.Array
    lpart: jax.Array
    rsrc: jax.Array
    rdst: jax.Array
    rw: jax.Array
    rpart: jax.Array
    vpart: jax.Array
    ldeg: jax.Array  # [n] int32 local arcs out of each vertex (duplicates too)
    rdeg: jax.Array  # [n] int32 remote arcs out of each vertex


def _device_arrays(pg: PartitionedGraph) -> _DeviceArrays:
    cached = pg.__dict__.get("_traversal_device_arrays")
    if cached is None:
        layout = partitioned_edge_layout(pg)
        n = pg.graph.n_vertices
        cached = _DeviceArrays(
            lsrc=jnp.asarray(layout.local.src),
            ldst=jnp.asarray(layout.local.dst),
            lw=jnp.asarray(layout.local.weights),
            lpart=jnp.asarray(layout.local_part),
            rsrc=jnp.asarray(layout.remote.src),
            rdst=jnp.asarray(layout.remote.dst),
            rw=jnp.asarray(layout.remote.weights),
            rpart=jnp.asarray(layout.remote_src_part),
            vpart=jnp.asarray(pg.part_of_vertex.astype(np.int32)),
            ldeg=jnp.asarray(
                np.bincount(layout.local.src, minlength=n).astype(np.int32)
            ),
            rdeg=jnp.asarray(
                np.bincount(layout.remote.src, minlength=n).astype(np.int32)
            ),
        )
        pg.__dict__["_traversal_device_arrays"] = cached
    return cached


def plane_arrays(pg: PartitionedGraph, program: VertexProgram):
    """Per-program ``(local, remote)`` edge-plane device arrays in the static
    layout's edge order, cached on the graph by ``program.plane_key``.

    ``plane_key == "graph"`` reuses the layout's own weight arrays; anything
    else asks the program for an ``[E]`` plane in original edge order and
    permutes it through the layout's retained sort permutation.
    """
    if program.plane_key == "graph":
        dev = _device_arrays(pg)
        return dev.lw, dev.rw
    cache = pg.__dict__.get("_plane_device_arrays")
    if not isinstance(cache, BoundedCache):
        cache = BoundedCache(_PLANE_CACHE_MAX)
        pg.__dict__["_plane_device_arrays"] = cache

    def build():
        plane = resolve_edge_plane(pg, program)  # O(E); only on cache miss
        layout = partitioned_edge_layout(pg)
        return (
            jnp.asarray(plane[layout.local_eid]),
            jnp.asarray(plane[layout.remote_eid]),
        )

    return cache.get_or_build(str(program.plane_key), build)


def make_superstep_fn(pg: PartitionedGraph) -> Callable[[jax.Array, jax.Array], SuperstepResult]:
    """Build the jitted one-superstep function for a fixed partitioned graph."""
    dev = _device_arrays(pg)
    lsrc, ldst, lw, lpart = dev.lsrc, dev.ldst, dev.lw, dev.lpart
    rsrc, rdst, rw, rpart = dev.rsrc, dev.rdst, dev.rw, dev.rpart
    v_part = dev.vpart
    n = pg.graph.n_vertices
    n_parts = pg.n_parts

    @jax.jit
    def superstep(dist: jax.Array, frontier: jax.Array) -> SuperstepResult:
        we0 = jnp.zeros(n_parts, jnp.int32)
        wv0 = jnp.zeros(n_parts, jnp.int32)

        def cond(carry):
            _, fr, _, _, _, _ = carry
            return fr.any()

        def body(carry):
            d, fr, we, wv, touched, it = carry
            active_e = fr[lsrc]
            cand = jnp.where(active_e, d[lsrc] + lw, jnp.inf)
            relaxed = jax.ops.segment_min(
                cand, ldst, num_segments=n, indices_are_sorted=True
            )
            new_d = jnp.minimum(d, relaxed)
            improved = new_d < d
            we = we + jax.ops.segment_sum(
                active_e.astype(jnp.int32), lpart, num_segments=n_parts
            )
            wv = wv + jax.ops.segment_sum(
                fr.astype(jnp.int32), v_part, num_segments=n_parts
            )
            return new_d, improved, we, wv, touched | improved, it + 1

        init = (dist, frontier, we0, wv0, frontier, jnp.int32(0))
        dist2, _, we, wv, touched, iters = jax.lax.while_loop(cond, body, init)

        # -- remote exchange at the superstep boundary ------------------------
        active_e = touched[rsrc]
        cand = jnp.where(active_e, dist2[rsrc] + rw, jnp.inf)
        relaxed = jax.ops.segment_min(
            cand, rdst, num_segments=n, indices_are_sorted=True
        )
        new_dist = jnp.minimum(dist2, relaxed)
        next_frontier = new_dist < dist2
        msgs = jax.ops.segment_sum(
            active_e.astype(jnp.int32), rpart, num_segments=n_parts
        )
        return SuperstepResult(new_dist, next_frontier, we, wv, msgs, iters)

    return superstep


class _WindowConsts(NamedTuple):
    """The dense window's static arrays, passed to it as one argument."""

    lsrc: jax.Array
    ldst: jax.Array
    lw: jax.Array  # program edge plane
    rsrc: jax.Array
    rdst: jax.Array
    rw: jax.Array
    vpart: jax.Array
    ldeg: jax.Array
    rdeg: jax.Array
    sg: jax.Array | None  # subgraph ids when collecting subgraphs
    lmap: tuple  # local-plane kernel block map (() on the xla backend)
    rmap: tuple  # remote-plane kernel block map


class TraversalResult(NamedTuple):
    """Raw device buffers from one batched traversal (one bulk transfer)."""

    dist: jax.Array  # [S, n] float32 final distances
    frontier: jax.Array  # [S, n] bool; non-empty only if m_max was hit
    n_supersteps: jax.Array  # [S] int32 supersteps each source actually ran
    edges_examined: jax.Array  # [S, m_max, P] int32
    verts_processed: jax.Array  # [S, m_max, P] int32
    msgs_sent: jax.Array  # [S, m_max, P] int32
    inner_iters: jax.Array  # [S, m_max] int32
    sg_active: jax.Array  # [S, m_max, n_sg] bool, or [S, m_max, 0] if off
    wire_msgs: jax.Array  # [S, m_max] int32 post-aggregation collective
    # messages per superstep (mesh mode; 0 on the dense path)

    def asdict(self) -> dict:
        """Schema-versioned named-field view (``graph.config``); the stable
        consumer surface -- field *order* above is not part of the contract."""
        from repro.graph.config import versioned_report

        return versioned_report("traversal_result", dict(self._asdict()))


class TraversalNotConverged(RuntimeError):
    """Raised by ``TraversalEngine.run`` when some source still has a
    non-empty frontier after ``m_max`` supersteps.  The partial
    ``TraversalResult`` is kept on ``.result`` (host-side numpy leaves)
    instead of being discarded."""

    def __init__(self, m_max: int, result: "TraversalResult"):
        self.result = result
        steps = np.asarray(result.n_supersteps).tolist()
        stuck = np.flatnonzero(result.frontier.any(axis=1)).tolist()
        super().__init__(
            f"BSP did not converge within {m_max} supersteps "
            f"(per-source n_supersteps={steps}, unconverged sources={stuck})"
        )


class WindowState(NamedTuple):
    """Device-resident carried state between windows (never pulled to host)."""

    dist: jax.Array  # [S, n] float32
    frontier: jax.Array  # [S, n] bool
    n_supersteps: jax.Array  # [S] int32, cumulative over all windows so far


class WindowResult(NamedTuple):
    """One window of supersteps: carried device state + the pulled counters.

    All counter fields are host numpy, fetched in ONE bulk ``device_get``;
    rows past ``n_supersteps`` (sources that converged mid-window) are zero.
    """

    state: WindowState  # device-resident; feed to the next run_window
    n_supersteps: np.ndarray  # [S] int32, cumulative (incl. this window)
    edges_examined: np.ndarray  # [S, k, P] int32
    verts_processed: np.ndarray  # [S, k, P] int32
    msgs_sent: np.ndarray  # [S, k, P] int32
    inner_iters: np.ndarray  # [S, k] int32
    part_active_next: np.ndarray  # [S, P] bool, parts active at the next superstep
    done: np.ndarray  # [S] bool, frontier empty (traversal converged)


#: Serving-path row surgery: one jitted scatter per coerced batch-shape key
#: (``(S, state_width, n_rows, dtype)``).  Each key gets its own ``jax.jit``
#: wrapper so evicting an entry also frees its compiled executable -- the
#: AL02 batch-shape cache discipline (bounded LRU, coerced keys).
_BACKFILL_FN_CACHE = BoundedCache(8)


def _backfill_impl(dist, frontier, nst, rows, f_dist, f_frontier, live, ident):
    """Scatter freshly-initialized batch rows into carried window state.

    ``rows`` indexes the batch axis; ``live`` marks rows that receive the
    matching fresh ``(f_dist, f_frontier)`` row, while dead rows are
    *deactivated*: state pinned at the program identity with an empty
    frontier, so a retired or requeued row stops contributing work (and
    counters) to subsequent windows.  ``n_supersteps`` restarts at 0 for
    every touched row.  Jitted at a distance via ``_BACKFILL_FN_CACHE``.
    """
    fd = jnp.where(live[:, None], f_dist, ident)
    ff = f_frontier & live[:, None]
    zeros = jnp.zeros(rows.shape, nst.dtype)
    return (
        dist.at[rows].set(fd),
        frontier.at[rows].set(ff),
        nst.at[rows].set(zeros),
    )


class TraversalEngine:
    """Device-resident multi-source BSP traversal over a static CSR layout.

    One call = one full traversal batch: the Python/host side contributes
    exactly two interactions -- launching the jitted ``while_loop`` and one
    bulk ``device_get`` of the final ``TraversalResult``.
    """

    def __init__(
        self,
        pg: PartitionedGraph,
        *,
        program: VertexProgram | None = None,
        device_of_part: np.ndarray | None = None,
        config: EngineConfig | None = None,
    ):
        cfg = config or EngineConfig()
        m_max = cfg.m_max
        collect_subgraphs = cfg.collect_subgraphs
        mesh = cfg.mesh
        backend = cfg.backend
        block_n, block_e = cfg.block_n, cfg.block_e
        mirror_degree = cfg.mirror_degree
        self.config = cfg
        self.pg = pg
        self.program = validate_program(program or SsspProgram())
        self.m_max = int(m_max)
        self.collect_subgraphs = bool(collect_subgraphs)
        self.n = pg.graph.n_vertices
        self.n_parts = pg.n_parts
        self.n_subgraphs = pg.n_subgraphs if collect_subgraphs else 0
        self.mesh = mesh
        # backend selects the segment-reduction implementation on the
        # superstep hot path: "xla" (segment ops; the default and the right
        # choice on CPU), "pallas" (the block-skipping relax kernels, TPU),
        # or "pallas-interpret" (kernel semantics on CPU -- CI parity mode).
        # Candidate gathers, counters, frontier logic, and collectives stay
        # on XLA under every backend, so counters and superstep counts are
        # bit-identical across backends.
        interpret = validate_backend(backend)
        self.backend = backend
        # hub mirroring is a mesh-layout concern; the dense engine has no
        # wire plane, so the knob only flows into the mesh program
        self.mirror_degree = (
            None if mirror_degree is None else int(mirror_degree)
        )
        with span("graph.engine_build"):
            self._mesh_prog = None
            if mesh is not None and int(mesh.devices.size) > 1:
                if collect_subgraphs:
                    raise NotImplementedError(
                        "collect_subgraphs is single-device-only; run the "
                        "metagraph ground-truth pass without a mesh"
                    )
                from repro.graph.mesh_exchange import MeshTraversalProgram

                self._mesh_prog = MeshTraversalProgram(
                    pg, mesh, device_of_part=device_of_part,
                    program=self.program, backend=backend,
                    block_n=block_n, block_e=block_e,
                    mirror_degree=self.mirror_degree,
                )
            # kernel backends: host-built relax fns over the static block maps
            # (None on xla, where the window runs XLA segment reductions)
            self._relax_l = self._relax_r = None
            # the dense window's static arrays travel as an argument: a jitted
            # function that closed over them would embed every edge array in the
            # compiled program as a constant, once per compiled (k, S) variant.
            # Mesh launches never trace the dense window and the mesh program
            # shards its own arrays, so a mesh engine uploads none of these.
            self._consts = None
            if self._mesh_prog is None:
                lmap = rmap = ()
                if backend != "xla":
                    layout = partitioned_edge_layout(pg)
                    self._relax_l, lmap = make_relax_fn(
                        layout.local.dst, self.n, reduce=self.program.reduce,
                        block_n=block_n, block_e=block_e, interpret=interpret,
                    )
                    self._relax_r, rmap = make_relax_fn(
                        layout.remote.dst, self.n, reduce=self.program.reduce,
                        block_n=block_n, block_e=block_e, interpret=interpret,
                    )
                dev = _device_arrays(pg)  # shared across engines on this graph
                lw, rw = plane_arrays(pg, self.program)
                sg = None
                if collect_subgraphs:
                    if "_sg_device" not in pg.__dict__:
                        pg.__dict__["_sg_device"] = jnp.asarray(
                            pg.subgraph_of_vertex.astype(np.int32)
                        )
                    sg = pg.__dict__["_sg_device"]
                self._consts = _WindowConsts(
                    dev.lsrc, dev.ldst, lw, dev.rsrc, dev.rdst, rw,
                    dev.vpart, dev.ldeg, dev.rdeg, sg, lmap, rmap,
                )
        # one jitted program serves both modes: run() launches a single
        # window of depth m_max, run_window() launches depth k (static arg,
        # compiled once per distinct k/S)
        self._window = jax.jit(self._window_impl, static_argnums=3)

    # -- state layout (identity on the dense path) ---------------------------

    @property
    def state_index_of_vertex(self) -> np.ndarray:
        """[n] index of each vertex in the carried state's trailing axis.

        The elastic executor uses this to address partition shards inside
        ``WindowState.dist`` without knowing whether the engine is dense
        (identity) or mesh-sharded (padded device-major positions).  The
        padded mapping itself lives in ONE place --
        ``MeshEdgeLayout.state_index_of_vertex`` -- shared by both engines.
        """
        if self._mesh_prog is not None:
            return self._mesh_prog.layout.state_index_of_vertex
        return np.arange(self.n, dtype=np.int64)

    def gather_global(self, state_rows: np.ndarray) -> np.ndarray:
        """Map host-side carried state ``[..., state_width]`` to global
        vertex order ``[..., n]`` (identity on the dense path; the padded
        gather is ``MeshEdgeLayout.gather_global``)."""
        if self._mesh_prog is not None:
            return self._mesh_prog.layout.gather_global(state_rows)
        return np.asarray(state_rows)

    def _launch(self, dist, frontier, nst0, k: int):
        """One window launch on whichever device program this engine runs."""
        if self._mesh_prog is not None:
            out = self._mesh_prog.window(dist, frontier, nst0, k)
            return TraversalResult(*out[:9]), out[9], out[10]
        return self._window(dist, frontier, nst0, k, self._consts)

    def window_jaxpr(self, *, k: int = 3, s_batch: int = 2):
        """Abstractly trace this engine's dense window program -- the exact
        fn ``_launch`` jits -- for the jaxpr auditor (``repro.analysis``).
        Mesh engines are traced device-free via
        ``mesh_exchange.abstract_window_jaxpr`` instead."""
        if self._mesh_prog is not None:
            raise NotImplementedError(
                "trace mesh engines with mesh_exchange.abstract_window_jaxpr"
            )
        sds = jax.ShapeDtypeStruct
        return jax.make_jaxpr(self._window_impl, static_argnums=3)(
            sds((s_batch, self.n), self.program.dtype),
            sds((s_batch, self.n), np.bool_),
            sds((s_batch,), np.int32),
            int(k),
            jax.tree.map(lambda a: sds(a.shape, a.dtype), self._consts),
        )

    def window_hlo(self, s_batch: int, k: int) -> str:
        """The compiled HLO text of the window program this engine launches
        for a batch of ``s_batch`` sources and a depth of ``k`` supersteps
        (``run`` launches ``k = m_max``).  Each instruction's ``op_name``
        metadata carries its device scope (``repro.spans``), which a
        profiler trace's op names are joined to.  Compiles, or loads from the
        persistent compile cache, the program a launch would run."""
        sds = jax.ShapeDtypeStruct
        if self._mesh_prog is not None:
            return self._mesh_prog.window_hlo(s_batch, k)
        state = (
            sds((s_batch, self.n), self.program.dtype),
            sds((s_batch, self.n), np.bool_),
            sds((s_batch,), np.int32),
        )
        lowered = self._window.lower(*state, int(k), self._consts)
        return lowered.compile().as_text()

    # -- device program ------------------------------------------------------

    def _window_impl(
        self, dist: jax.Array, frontier: jax.Array, nst0: jax.Array, m_max: int,
        consts: "_WindowConsts",
    ):
        s_batch = dist.shape[0]
        (lsrc, ldst, lw, rsrc, rdst, rw, vpart, ldeg, rdeg, sg, lmap,
         rmap) = consts
        n, p = self.n, self.n_parts
        prog = self.program
        ident = prog.identity
        seg_red = (
            jax.ops.segment_min if prog.reduce == "min" else jax.ops.segment_sum
        )

        seg_red_l = jax.vmap(
            lambda c: seg_red(
                c, ldst, num_segments=n, indices_are_sorted=True
            )
        )
        seg_red_r = jax.vmap(
            lambda c: seg_red(
                c, rdst, num_segments=n, indices_are_sorted=True
            )
        )

        # every value reduction funnels through these two: base=None is the
        # bare segment reduce (stationary accumulate), base=state fuses the
        # program combine.  The pallas backends run both forms as one
        # block-skipping kernel pass (base <- identity when None); the xla
        # forms below are the exact pre-backend expressions.
        def relax_l(cand, base=None):
            if self._relax_l is not None:
                if base is None:
                    base = jnp.full((cand.shape[0], n), ident, dist.dtype)
                return self._relax_l(lmap, cand, base)
            r = seg_red_l(cand)
            return r if base is None else prog.combine(base, r)

        def relax_r(cand, base=None):
            if self._relax_r is not None:
                if base is None:
                    base = jnp.full((cand.shape[0], n), ident, dist.dtype)
                return self._relax_r(rmap, cand, base)
            r = seg_red_r(cand)
            return r if base is None else prog.combine(base, r)

        def part_sum(x):
            # [S, n] int32 -> [S, P]: one dense reduction over the vertex
            # axis, no scatter.  With x a per-vertex degree under a frontier
            # it is the per-arc count, since every arc counts toward its
            # source's partition.
            in_part = vpart[None, None, :] == jnp.arange(p)[None, :, None]
            return jnp.sum(
                jnp.where(in_part, x[:, None, :], 0), axis=-1, dtype=jnp.int32
            )

        def sendable(state, frontier):
            # [S, n]: the frontier folded into the state before a plane's
            # [S, E] gather.  ``relax`` maps ``identity`` to ``identity`` (the
            # VertexProgram contract, rule JX05), so
            # ``relax(sendable[:, src], w)`` is bit for bit
            # ``where(frontier[:, src], relax(state[:, src], w), identity)``,
            # from one gather per arc in place of two.  The barrier keeps the
            # select out of the gather's fusion, so the gather reads one
            # materialised buffer rather than both operands per index.
            return jax.lax.optimization_barrier(
                jnp.where(frontier, state, ident)
            )

        n_sg = self.n_subgraphs
        if self.collect_subgraphs:
            seg_any_sg = jax.vmap(
                lambda f: jax.ops.segment_max(
                    f.astype(jnp.int32), sg, num_segments=n_sg
                )
                > 0
            )

        def stationary_body(carry):
            # one gather pass over local + remote edges, program.apply at the
            # boundary, frontier drained by the iteration budget
            s, d, fr, we, wv, ms, it, sg, nst = carry
            with scope("local", "counters"):
                if self.collect_subgraphs:
                    sg = jax.lax.dynamic_update_index_in_dim(
                        sg, seg_any_sg(fr), s, axis=1
                    )
            with scope("local", "frontier"):
                nst = nst + fr.any(axis=1).astype(jnp.int32)

            with scope("local", "gather"):
                snd = sendable(d, fr)  # both planes read it
                cand = prog.relax(snd[:, lsrc], lw)
            with scope("local", "relax"):
                acc = relax_l(cand)
            with scope("local", "counters"):
                we_s = part_sum(jnp.where(fr, ldeg, 0))
                wv_s = part_sum(fr.astype(jnp.int32))
                it_s = fr.any(axis=1).astype(jnp.int32)  # one pass per superstep

            with scope("remote", "gather"):
                cand_r = prog.relax(snd[:, rsrc], rw)
            with scope("remote", "relax"):
                acc = relax_r(cand_r, acc)
            with scope("remote", "counters"):
                ms_s = part_sum(jnp.where(fr, rdeg, 0))

            with scope("remote", "frontier"):
                new_d = prog.apply(d, acc, n)
                next_fr = fr & prog.keep_running(nst)[:, None]
                s_next = s + 1

            with scope("remote", "counters"):
                we = jax.lax.dynamic_update_index_in_dim(we, we_s, s, axis=1)
                wv = jax.lax.dynamic_update_index_in_dim(wv, wv_s, s, axis=1)
                ms = jax.lax.dynamic_update_index_in_dim(ms, ms_s, s, axis=1)
                it = jax.lax.dynamic_update_index_in_dim(it, it_s, s, axis=1)
            return s_next, new_d, next_fr, we, wv, ms, it, sg, nst

        def monotone_body(carry):
            s, d, fr, we, wv, ms, it, sg, nst = carry

            with scope("local", "counters"):
                if self.collect_subgraphs:
                    sg = jax.lax.dynamic_update_index_in_dim(
                        sg, seg_any_sg(fr), s, axis=1
                    )
            with scope("local", "frontier"):
                nst = nst + fr.any(axis=1).astype(jnp.int32)

            # -- local closure over the partition-local edges -----------------
            def icond(c):
                with scope("local", "frontier"):
                    return c[1].any()

            def ibody(c):
                d_i, f_i, we_s, wv_s, it_s, touched = c
                with scope("local", "gather"):
                    cand = prog.relax(sendable(d_i, f_i)[:, lsrc], lw)
                with scope("local", "relax"):
                    new_d = relax_l(cand, d_i)
                with scope("local", "frontier"):
                    improved = prog.is_active(new_d, d_i)
                    touched = touched | improved
                with scope("local", "counters"):
                    we_s = we_s + part_sum(jnp.where(f_i, ldeg, 0))
                    wv_s = wv_s + part_sum(f_i.astype(jnp.int32))
                    it_s = it_s + f_i.any(axis=1).astype(jnp.int32)
                return new_d, improved, we_s, wv_s, it_s, touched

            with scope("local", "counters"):
                z_p = jnp.zeros((s_batch, p), jnp.int32)
                z_s = jnp.zeros((s_batch,), jnp.int32)
            d2, _, we_s, wv_s, it_s, touched = jax.lax.while_loop(
                icond, ibody, (d, fr, z_p, z_p, z_s, fr)
            )

            # -- remote exchange at the superstep boundary --------------------
            with scope("remote", "gather"):
                cand = prog.relax(sendable(d2, touched)[:, rsrc], rw)
            with scope("remote", "relax"):
                new_d = relax_r(cand, d2)
            with scope("remote", "frontier"):
                next_fr = prog.is_active(new_d, d2)
                s_next = s + 1
            with scope("remote", "counters"):
                ms_s = part_sum(jnp.where(touched, rdeg, 0))
                we = jax.lax.dynamic_update_index_in_dim(we, we_s, s, axis=1)
                wv = jax.lax.dynamic_update_index_in_dim(wv, wv_s, s, axis=1)
                ms = jax.lax.dynamic_update_index_in_dim(ms, ms_s, s, axis=1)
                it = jax.lax.dynamic_update_index_in_dim(it, it_s, s, axis=1)
            return s_next, new_d, next_fr, we, wv, ms, it, sg, nst

        superstep_body = stationary_body if prog.stationary else monotone_body

        def superstep_cond(carry):
            s, _, fr, *_ = carry
            with scope("remote", "frontier"):
                return (s < m_max) & fr.any()

        with scope("local", "counters"):
            zeros_smp = jnp.zeros((s_batch, m_max, p), jnp.int32)
            init = (
                jnp.int32(0),
                dist,
                frontier,
                zeros_smp,
                zeros_smp,
                zeros_smp,
                jnp.zeros((s_batch, m_max), jnp.int32),
                jnp.zeros((s_batch, m_max, n_sg), bool),
                nst0,
            )
        _, d, fr, we, wv, ms, it, sg, nst = jax.lax.while_loop(
            superstep_cond, superstep_body, init
        )
        # next-superstep partition activity + done flags, computed on device
        # so the executor's placement decision needs no extra [n]-sized pull
        with scope(FINISH):
            pact = part_sum(fr.astype(jnp.int32)) > 0
            done = ~fr.any(axis=1)
            wire = jnp.zeros((s_batch, m_max), jnp.int32)  # dense: no wire
        return TraversalResult(d, fr, nst, we, wv, ms, it, sg, wire), pact, done

    # -- host API ------------------------------------------------------------

    def init_state(self, sources) -> WindowState:
        """Device-resident initial state for ``run_window`` (no host sync).

        The program defines the initial ``(state, frontier)`` in global
        vertex order (``sources`` sizes the batch for source-free programs
        like WCC/PageRank); in mesh mode the state is scattered into the
        padded device-major layout, already sharded over the partition axis.
        """
        sources = np.atleast_1d(np.asarray(sources, dtype=np.int64))
        s_batch = sources.shape[0]
        with span("graph.init"):
            if self._mesh_prog is not None:
                dist, frontier = self._mesh_prog.init_state(sources)
            else:
                state, frontier = self.program.init(self.pg, sources)
                dist, frontier = jnp.asarray(state), jnp.asarray(frontier)
            return WindowState(dist, frontier, jnp.zeros((s_batch,), jnp.int32))

    def backfill_rows(self, state: WindowState, rows, sources) -> WindowState:
        """Replace carried-state batch rows at a window boundary (in place of
        re-initializing the whole batch -- the serving micro-batcher's
        retire/backfill surgery).

        ``sources[i] >= 0`` re-initializes row ``rows[i]`` from that source
        through ``program.init`` -- bit-identical to the row a fresh
        ``init_state`` batch would carry, because the window math is
        row-independent (the batcher's backfill test pins this).
        ``sources[i] == -1`` *deactivates* the row: identity state, empty
        frontier, so it contributes no further work or counters.  Either way
        the row's ``n_supersteps`` restarts at 0.

        In mesh mode the fresh rows are scattered through the same padded
        device-major permutation the relayout machinery uses
        (``MeshTraversalProgram.init_state`` routes ``pos_of_vertex``), and
        the surgered state is re-committed to the engine's active sharding;
        the surgery assumes the state is laid out for the engine's *current*
        ``device_of_part`` (run any re-layout first).
        """
        rows = np.atleast_1d(np.asarray(rows, dtype=np.int64))
        sources = np.atleast_1d(np.asarray(sources, dtype=np.int64))
        if rows.shape != sources.shape:
            raise ValueError(
                f"rows {rows.shape} and sources {sources.shape} must match"
            )
        if rows.size == 0:
            return state
        s_batch = int(state.dist.shape[0])
        if np.unique(rows).size != rows.size or (rows < 0).any() or (
            rows >= s_batch
        ).any():
            raise ValueError(f"rows must be unique in [0, {s_batch}): {rows}")
        live = sources >= 0
        fresh = self.init_state(np.where(live, sources, 0))
        key = (
            s_batch,
            int(state.dist.shape[1]),
            int(rows.size),
            str(np.dtype(self.program.dtype)),
        )
        fn = _BACKFILL_FN_CACHE.get_or_build(key, lambda: jax.jit(_backfill_impl))
        ident = jnp.asarray(self.program.identity, state.dist.dtype)
        dist, frontier, nst = fn(
            state.dist, state.frontier, state.n_supersteps,
            jnp.asarray(rows), fresh.dist, fresh.frontier,
            jnp.asarray(live), ident,
        )
        if self._mesh_prog is not None:
            # pin the surgered state back to the engine's canonical sharding
            # (scatter output sharding is compiler-chosen; this is a no-copy
            # commit when the compiler already kept it sharded)
            dist = jax.device_put(dist, state.dist.sharding)
            frontier = jax.device_put(frontier, state.frontier.sharding)
        return WindowState(dist, frontier, nst)

    @property
    def device_of_part(self) -> np.ndarray | None:
        """The *active* partition -> device map (mesh mode; None dense).

        This is the compute placement the next window will run on -- dynamic
        re-layout (``run_window(..., device_of_part=...)``) changes it
        between windows."""
        if self._mesh_prog is not None:
            return self._mesh_prog.layout.device_of_part
        return None

    def run_window(
        self,
        state: WindowState,
        k: int,
        *,
        device_of_part: np.ndarray | None = None,
    ) -> WindowResult:
        """Run up to ``k`` more supersteps from ``state`` in one device launch.

        Sources whose frontier empties mid-window simply stop contributing
        counter rows (no convergence raise -- check ``done``).  The returned
        counters are the window's ONE bulk host transfer; carried
        dist/frontier stay on device in ``.state``.

        ``device_of_part`` (mesh mode) re-lays the *compute* out before the
        launch: the engine swaps to the matching ``MeshEdgeLayout``
        (incrementally rebuilt, consts/jit LRU-cached) and the carried state
        is remapped exactly (``mesh_exchange.relayout_state``), so results
        stay bit-identical to a static-layout run while the work executes on
        the requested devices.  The dense path has a single device and
        ignores the override.
        """
        k = int(k)
        if k < 1:
            raise ValueError(f"window size must be >= 1, got {k}")
        if device_of_part is not None and self._mesh_prog is not None:
            with span("graph.relayout"):
                state, _ = self._mesh_prog.ensure_layout(state, device_of_part)
        with span("graph.launch"):
            res, pact, done = self._launch(
                state.dist, state.frontier, state.n_supersteps, k
            )
        with span("graph.fetch"):
            nst, we, wv, ms, it, pact, done = jax.device_get(
                (
                    res.n_supersteps,
                    res.edges_examined,
                    res.verts_processed,
                    res.msgs_sent,
                    res.inner_iters,
                    pact,
                    done,
                )
            )
        return WindowResult(
            state=WindowState(res.dist, res.frontier, res.n_supersteps),
            n_supersteps=nst,
            edges_examined=we,
            verts_processed=wv,
            msgs_sent=ms,
            inner_iters=it,
            part_active_next=pact,
            done=done,
        )

    def run(self, sources) -> TraversalResult:
        """Run one batched traversal from ``sources`` (host ints).

        Returns the *host-side* ``TraversalResult`` (numpy leaves) -- the one
        bulk transfer of the whole execution.  Raises ``TraversalNotConverged``
        (with the partial result attached and per-source ``n_supersteps`` in
        the message) if any source failed to converge within ``m_max``
        supersteps.
        """
        state = self.init_state(sources)
        with span("graph.launch"):
            res, _, _ = self._launch(
                state.dist, state.frontier, state.n_supersteps, self.m_max
            )
        with span("graph.fetch"):
            res = jax.device_get(res)
            if self._mesh_prog is not None:
                # padded device-major -> global vertex order for host consumers
                res = res._replace(
                    dist=self.gather_global(res.dist),
                    frontier=self.gather_global(res.frontier),
                )
        if not self.program.converged(bool(res.frontier.any())):
            raise TraversalNotConverged(self.m_max, res)
        return res


def get_engine(
    pg: PartitionedGraph,
    *,
    program: VertexProgram | None = None,
    config: EngineConfig | None = None,
) -> TraversalEngine:
    """Per-graph engine cache (keyed by the knobs, stored on the instance).

    Engines are keyed by ``program.key`` (default ``SsspProgram``), the
    compute ``backend`` (``"xla"`` | ``"pallas"`` | ``"pallas-interpret"``,
    see ``TraversalEngine``), the mesh-mode ``mirror_degree`` hub threshold
    and, in mesh mode, the mesh's device ids; the default balanced
    contiguous partition map is assumed (construct ``TraversalEngine``
    directly for a custom ``device_of_part``).  Knobs come from ``config``
    (an ``EngineConfig``; None means ``EngineConfig()``).
    """
    cfg = config or EngineConfig()
    engines = pg.__dict__.get("_traversal_engines")
    if not isinstance(engines, BoundedCache):
        engines = BoundedCache(_ENGINE_CACHE_MAX)
        pg.__dict__["_traversal_engines"] = engines
    mesh_key = (
        None
        if cfg.mesh is None
        else tuple(int(d.id) for d in cfg.mesh.devices.flat)
    )
    prog_key = (program or SsspProgram()).key
    mirror_key = (
        None if cfg.mirror_degree is None else int(cfg.mirror_degree)
    )
    key = (
        int(cfg.m_max), bool(cfg.collect_subgraphs), mesh_key, prog_key,
        str(cfg.backend), mirror_key,
    )
    return engines.get_or_build(
        key,
        lambda: TraversalEngine(pg, program=program, config=cfg),
    )


# -- numpy reference implementations (test oracles) ---------------------------


def _bellman_ford(
    n: int, src: np.ndarray, dst: np.ndarray, w: np.ndarray, source: int
) -> np.ndarray:
    dist = np.full(n, np.inf, dtype=np.float64)
    dist[source] = 0.0
    for _ in range(n):
        cand = dist[src] + w
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist):
            break
        dist = new
    return dist


def reference_bfs(pg: PartitionedGraph, source: int) -> np.ndarray:
    """Hop-count oracle: BFS levels regardless of any edge weights."""
    g = pg.graph
    return _bellman_ford(
        g.n_vertices, g.src, g.dst, np.ones(g.n_edges, dtype=np.float64), source
    )


def reference_sssp(pg: PartitionedGraph, source: int) -> np.ndarray:
    """*Weighted* shortest-path oracle (Bellman-Ford over ``edge_weights``).

    On a graph without a weight plane the unit default makes this coincide
    with ``reference_bfs`` -- call that one when hop counts are what's meant.
    """
    g = pg.graph
    return _bellman_ford(
        g.n_vertices, g.src, g.dst, g.edge_weights.astype(np.float64), source
    )


def reference_wcc(pg: PartitionedGraph) -> np.ndarray:
    """Min-label-propagation oracle: for each vertex, the smallest vertex id
    reachable by repeatedly following directed edges under min -- on the
    symmetrized graphs the generators produce, the smallest id in its
    weakly-connected component (matches ``WccProgram`` exactly)."""
    g = pg.graph
    labels = np.arange(g.n_vertices, dtype=np.int64)
    while True:
        new = labels.copy()
        np.minimum.at(new, g.dst, labels[g.src])
        if np.array_equal(new, labels):
            return labels
        labels = new


def reference_pagerank(
    pg: PartitionedGraph, damping: float = 0.85, num_iters: int = 20
) -> np.ndarray:
    """Power-iteration oracle matching ``PageRankProgram``: fixed budget,
    no dangling-mass redistribution (symmetrized graphs have none), float64."""
    g = pg.graph
    n = g.n_vertices
    contrib_w = 1.0 / np.maximum(g.out_degree, 1).astype(np.float64)
    rank = np.full(n, 1.0 / n, dtype=np.float64)
    for _ in range(num_iters):
        acc = np.zeros(n, dtype=np.float64)
        np.add.at(acc, g.dst, rank[g.src] * contrib_w[g.src])
        rank = (1.0 - damping) / n + damping * acc
    return rank
