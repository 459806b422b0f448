"""Mesh-sharded traversal: the superstep-boundary exchange as a real
collective over a 1-D device mesh.

``MeshTraversalProgram`` is the multi-device twin of
``TraversalEngine._window_impl``: the whole window (outer superstep loop,
inner local-closure loop, remote exchange, counter accumulation) runs inside
ONE ``shard_map`` over ``dist.sharding.partition_mesh`` -- each device owns a
fixed-shape padded vertex shard (``MeshEdgeLayout``) and the program is pure
SPMD.  The per-edge math and every aggregation point route through a
``graph.program.VertexProgram`` (default ``SsspProgram`` -- BFS semantics on
unit weights, bit-identical to the pre-algebra program):

  * **local closure** (monotone programs): every device relaxes its own
    partitions' local edges under ``program.relax``/``combine``; iteration
    count is synchronized with a ``pmax`` of the per-device "anything
    improved" bit, so the loop structure (and hence the work counters) is
    bit-identical to the single-device engine.  Stationary programs
    (PageRank) instead take one gather pass per superstep and fold the
    accumulated messages with ``program.apply`` at the boundary.
  * **remote exchange**: candidate messages over this device's remote
    out-edges are ``combine``-aggregated into static wire slots **before**
    the collective -- one message per ``(dst_vertex, dst_device)`` block
    entry, not one per edge (the Spinner/message-combining structure, arXiv
    1404.3861 / 1503.00626; combiner aggregation is algorithm-generic, so
    min-programs and sum-programs share the machinery) -- then a single
    static-shape ``jax.lax.all_to_all`` delivers every ``[n_devices, w_pad]``
    buffer, and a scatter (``.min`` or ``.add`` per ``program.reduce``)
    applies the received aggregates to the local shard.  Padded slots carry
    the program's ``identity`` and are no-ops by construction.
  * **counters**: each device accumulates the ``[S, k, P]`` work counters for
    its own partitions only (partitions never span devices), so one ``psum``
    per window reconstructs the exact global integers.  They come from
    per-vertex degrees, not from the per-arc frontier: every arc counts
    toward its source's partition, so the arcs a frontier examines, by
    partition, are ``part_sum(where(frontier, deg, 0))`` -- one dense masked
    sum of ``[S, n_pad]`` over the vertex axis against ``part_of_pos``, with
    no scatter into partition bins.  ``build_window_consts`` derives the
    ``[D, n_pad]`` degrees (``ldeg``/``rdeg``/``mdeg``: each device's valid
    local, remote and mirror arcs out of each padded position, 0 on padding)
    from whatever layout is active.  ``wire_msgs`` counts
    the non-identity slots actually put on the collective per superstep (for
    sum programs: slots fed by at least one active edge) -- the
    post-aggregation message volume the bench compares against the raw
    remote-edge count.

The program preserves the engine's windowed contract exactly: same
``(dist, frontier, nst0, k) -> (result..., part_active_next, done)``
signature, state dtype per ``program.dtype``, and state/counters
bit-identical to the dense path for monotone programs (min and integer sums
are order-independent; float sums reassociate, so stationary state matches
only to rounding while its integer counters stay exact).  The carried state
is the *padded device-major* layout ``[S, n_devices * n_pad]``;
``MeshEdgeLayout.gather_global`` maps it back to vertex order.

**Hub mirroring** (``mirror_degree``, threaded from ``TraversalEngine``):
when the layout was built with a degree threshold that selects hubs
(``partition.mesh_edge_layout``), remote edges targeting a hub are rewritten
at layout-build time to feed a device-local *mirror* slot instead of a wire
slot, and each superstep runs a SECOND static-shape ``all_to_all`` that
syncs one value per ``(device, hub)`` block entry to the hub's owner -- the
mirrored collective signature
(``VertexProgram.collective_signature(mirrored=True)`` declares
``all_to_all: 2``; the JX02 auditor checks the trace against it).  For
monotone programs the mirror is *stateful* within a window: a per-device
cache ``[S, n_devices * m_pad]`` carries the best value ever combined into
each mirror, and a slot is synced only when its cache value improves.  This
is exact: a value is sent the superstep it improves, so the owner's state is
always <= the cache, and a suppressed candidate (>= cache >= owner state)
could never have changed the owner under ``min`` -- state, frontier, and
every counter except ``wire_msgs`` stay bit-identical to the unmirrored
path, while ``wire_msgs`` (which bills non-identity slots across BOTH
collectives) drops by exactly the suppressed re-sends.  Stationary programs
get no cache (``apply`` is arbitrary, so every superstep's aggregate must
arrive): the mirror plane syncs its fed slots each superstep and
``wire_msgs`` is unchanged vs the unmirrored path.  ``mirror_degree=None``
(default) and zero-hub graphs trace the byte-identical unmirrored program
(``m_pad == 0`` statically removes the cache, the second collective, and
the mirror constants' use).

Physical shard placement for the elastic executor lives here too:
``place_shard`` moves a partition's state array onto a target device and
reports whether bytes actually crossed devices -- the executor's per-window
resharding seam.

**Dynamic re-layout** (the compute plane following the planner): the program
is no longer married to the ``device_of_part`` it was built with.
``MeshTraversalProgram.ensure_layout(state, device_of_part)`` swaps the
active ``MeshEdgeLayout`` between windows -- per-layout device constants are
LRU-cached (``layout_cache_size``), the jitted window program is keyed by the
layout's static shapes so a swap re-jits at most once per distinct layout
shape (``window_cache_size`` LRU), and the carried state is remapped by
``relayout_state``: a pure gather/scatter permutation between the two padded
device-major layouts, so the *global* state is bit-identical across the swap
(padding rows re-filled with the program identity; the replicated
``n_supersteps`` budget rides along untouched).  The bytes such a remap
moves between devices are the executor's *physical* ledger
(``device_moves``/``device_move_bytes``); the *billed* cloud migration
(``CostReport.migration_secs``) stays derived from the placement plan alone
and is therefore device-count-independent -- see ``core.elastic`` for the
two-ledger contract.

**Compute backend** (``backend`` kwarg, threaded from ``TraversalEngine``):
with ``backend="pallas"`` / ``"pallas-interpret"`` the two per-device value
reductions on the superstep hot path -- the local-edge reduction over
``n_pad`` rows and the pre-all-to-all wire-slot aggregation over
``n_devices * w_pad`` slots -- run through the block-skipping Pallas relax
kernel (``kernels.bfs_relax``) instead of XLA segment ops.  Each device
shard's problem is exactly the kernel's shape: ``ldst[d]`` and ``rslot[d]``
are ascending (padding rows carry ``n_pad - 1`` / ``D * w_pad - 1`` -- real
rows fed identity candidates), so the per-device static block maps
(``MeshEdgeLayout.local_block_map`` / ``wire_block_map``, carried through
the incremental rebuild) bound each row block's edge-block span.  The maps
ride along as four extra sharded constants keyed into the same per-layout
const cache; counters, ``seg_any_wire``, receive scatters, and the
collective stay on XLA, so counters and superstep counts are bit-identical
across backends (monotone state bit-identical; stationary sums reassociate
across tile order, so state matches to rounding).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.dist.sharding import (
    PARTS,
    per_device_sharding,
    per_device_spec,
    traversal_state_sharding,
    traversal_state_spec,
)
from repro.graph.partition import (
    contiguous_device_map,
    mesh_edge_layout,
    partitioned_edge_layout,
)
from repro.graph.program import (
    SsspProgram,
    VertexProgram,
    resolve_edge_plane,
    validate_collective_signature,
    validate_program,
)
from repro.graph.structs import BoundedCache, MeshEdgeLayout, PartitionedGraph
from repro.kernels.bfs_relax.ops import (
    _block_dims,
    relax_blockmap_call,
    validate_backend,
)
from repro.spans import FINISH, collective, scope
from jax.sharding import PartitionSpec as P

#: collectives ``_body`` contributes OUTSIDE the superstep loop -- the
#: counter-reconstruction epilogue: five counter/flag psums (we, wv, ms,
#: wire, pact) plus the final ``done`` pmax.  The per-superstep collectives
#: are declared by ``VertexProgram.collective_signature()``; together they
#: are the full expected collective footprint the jaxpr auditor
#: (``repro.analysis.jaxpr_audit``, rule JX02) checks the trace against.
MESH_WINDOW_EPILOGUE = {"psum": 5, "pmax": 1}

#: the outer superstep loop's condition syncs the global any-active bit once
#: per evaluation -- a device-local cond would let iteration counts diverge
MESH_SUPERSTEP_COND = {"pmax": 1}

#: default LRU bounds for the per-layout const uploads and jitted windows
#: (the PR 5 cache policy the recompile-budget audit, rule JX04, holds
#: scripted relayout/window sweeps to)
DEFAULT_LAYOUT_CACHE_SIZE = 4
DEFAULT_WINDOW_CACHE_SIZE = 8


def mesh_size(mesh: Mesh) -> int:
    return int(mesh.devices.size)


def plane_shards(pg: PartitionedGraph, program: VertexProgram, ml: MeshEdgeLayout):
    """Per-device ``(lw, rw, mw)`` edge planes for a program: the layout's
    own weights for ``plane_key == "graph"``, else the program's ``[E]``
    plane permuted through the retained layout/shard edge ids."""
    plane = resolve_edge_plane(pg, program)
    if plane is None:
        return ml.lw, ml.rw, ml.mw
    pel = partitioned_edge_layout(pg)
    plane_l = plane[pel.local_eid]  # dst-sorted local order
    plane_r = plane[pel.remote_eid]  # dst-sorted remote order
    lw = np.where(ml.lvalid, plane_l[ml.l_eid], 0.0).astype(np.float32)
    rw = np.where(ml.rvalid, plane_r[ml.r_eid], 0.0).astype(np.float32)
    mw = np.where(ml.mvalid, plane_r[ml.m_eid], 0.0).astype(np.float32)
    return lw, rw, mw


def out_degrees(src: np.ndarray, valid: np.ndarray, width: int) -> np.ndarray:
    """``[D, width]`` int32: each device's valid arcs out of each of its
    padded vertex positions, duplicate arcs included (0 on padding)."""
    return np.stack([
        np.bincount(s[v], minlength=width) for s, v in zip(src, valid)
    ]).astype(np.int32)


def build_window_consts(
    pg: PartitionedGraph,
    program: VertexProgram,
    ml: MeshEdgeLayout,
    *,
    backend: str = "xla",
    block_n: int = 512,
    block_e: int = 512,
):
    """Host-side ``(consts, statics)`` of one window program: the sharded
    constant tables ``_body`` consumes (in its positional order) plus the
    static block geometry for the kernel backend.

    The single source of truth for the window's constant signature, shared
    by ``MeshTraversalProgram._activate`` (which uploads the arrays) and the
    jaxpr auditor's abstract trace (which only needs their shapes/dtypes) --
    so the audited program is the deployed program by construction.
    """
    lw, rw, mw = plane_shards(pg, program, ml)
    # the work counters read each plane's arcs only as per-vertex degrees
    # (``_body``'s ``part_sum``), so no per-arc partition id is uploaded; the
    # degrees are derived here, from the layout as it stands, so a relayout,
    # an incremental rebuild or a delta merge can never leave them stale.
    # The unmirrored program never reads ``mdeg``: zero-width, like the
    # other mirror constants there.
    consts = (
        ml.lsrc, ml.ldst, lw, out_degrees(ml.lsrc, ml.lvalid, ml.n_pad),
        ml.lvalid, ml.part_of_pos,
        ml.rsrc, rw, ml.rslot, out_degrees(ml.rsrc, ml.rvalid, ml.n_pad),
        ml.rvalid, ml.recv_idx,
        ml.msrc, mw, ml.mslot,
        out_degrees(ml.msrc, ml.mvalid, ml.n_pad if ml.m_pad else 0),
        ml.mvalid, ml.mrecv_idx,
    )
    statics = None
    if backend != "xla":
        # per-device static block maps for the kernel backend: one geometry
        # per reduction plane (local rows vs wire slots vs mirror slots),
        # clamped exactly as relax_blockmap_call will re-derive them
        d_n = ml.n_devices
        bn_l, be_l, _, _ = _block_dims(
            ml.n_pad, ml.e_local_pad, block_n, block_e
        )
        bn_w, be_w, _, _ = _block_dims(
            d_n * ml.w_pad, ml.e_remote_pad, block_n, block_e
        )
        ls, lc, lt = ml.local_block_map(bn_l, be_l)
        ws, wc, wt = ml.wire_block_map(bn_w, be_w)
        consts = consts + (ls, lc, ws, wc)
        statics = (bn_l, be_l, lt, bn_w, be_w, wt)
        if ml.m_pad > 0:
            bn_m, be_m, _, _ = _block_dims(
                d_n * ml.m_pad, ml.e_mirror_pad, block_n, block_e
            )
            ms, mc, mt = ml.mirror_block_map(bn_m, be_m)
            consts = consts + (ms, mc)
            statics = statics + (bn_m, be_m, mt)
    return consts, statics


def window_cache_key(ml: MeshEdgeLayout, m_max: int, backend: str, statics) -> tuple:
    """Canonical jit-cache key of one window program.

    The traced fn depends on the layout only through these static shapes
    (constants are arguments), so shape-identical layouts -- the common
    re-layout case -- share one compiled program.  Shared by
    ``MeshTraversalProgram.window`` and the recompile-budget audit (rule
    JX04), which asserts a scripted relayout/window sweep stays within
    ``DEFAULT_WINDOW_CACHE_SIZE`` distinct keys.
    """
    return (
        int(m_max), ml.n_pad, ml.w_pad, ml.e_local_pad, ml.e_remote_pad,
        ml.m_pad, ml.e_mirror_pad, str(backend), statics,
    )


def window_body(
    pg: PartitionedGraph,
    program: VertexProgram,
    ml: MeshEdgeLayout,
    m_max: int,
    *,
    backend: str = "xla",
    statics=None,
):
    """``_body`` closed over its static parameters for one (layout, m_max) --
    what ``shard_map`` maps, shared by ``MeshTraversalProgram._build`` and
    ``abstract_window_jaxpr``."""
    return partial(
        MeshTraversalProgram._body,
        m_max=int(m_max), n_parts=pg.n_parts, n_pad=ml.n_pad,
        w_pad=ml.w_pad, d_n=ml.n_devices, m_pad=ml.m_pad, prog=program,
        n_global=pg.graph.n_vertices, backend=backend, statics=statics,
    )


def abstract_window_jaxpr(
    pg: PartitionedGraph,
    program: VertexProgram | None = None,
    *,
    d_n: int,
    m_max: int = 3,
    s_batch: int = 2,
    backend: str = "xla",
    device_of_part: np.ndarray | None = None,
    block_n: int = 512,
    block_e: int = 512,
    mirror_degree: int | None = None,
):
    """Abstractly trace the mesh window over ``d_n`` *abstract* devices.

    Builds the exact ``shard_map`` program ``MeshTraversalProgram._build``
    would compile -- same body, same constant signature via
    ``build_window_consts`` -- but over ``jax.sharding.AbstractMesh``, so the
    jaxpr auditor can walk the real SPMD trace (collectives, Pallas grids,
    host callbacks) in a single-device CI job with zero mesh devices.
    """
    from jax.sharding import AbstractMesh

    program = validate_program(program or SsspProgram())
    validate_backend(backend)
    if device_of_part is None:
        device_of_part = contiguous_device_map(pg.n_parts, d_n)
    ml = mesh_edge_layout(pg, device_of_part, d_n, mirror_degree=mirror_degree)
    consts, statics = build_window_consts(
        pg, program, ml, backend=backend, block_n=block_n, block_e=block_e
    )
    body = window_body(pg, program, ml, m_max, backend=backend, statics=statics)
    state = traversal_state_spec()
    rep = P()
    mapped = jax.shard_map(
        body,
        mesh=AbstractMesh((int(d_n),), (PARTS,)),
        in_specs=(state, state, rep)
        + tuple(per_device_spec(np.ndim(c)) for c in consts),
        out_specs=(state, state) + (rep,) * 9,
        check_vma=False,
    )
    sds = jax.ShapeDtypeStruct
    args = (
        sds((s_batch, ml.state_width), program.dtype),
        sds((s_batch, ml.state_width), np.bool_),
        sds((s_batch,), np.int32),
    ) + tuple(sds(np.shape(c), np.asarray(c).dtype) for c in consts)
    return jax.make_jaxpr(mapped)(*args)


def place_shard(
    x: jax.Array, device, prev_device=None
) -> tuple[jax.Array, bool]:
    """Commit ``x`` to ``device``; True when the shard changed devices.

    ``prev_device`` is where this shard resided before the move (``None`` for
    the initial placement, which is never a move).  The returned flag marks
    bytes a real deployment would put on the interconnect -- a device-to-
    device transfer, as opposed to a refresh of a shard already resident on
    its target -- which is what lets the elastic executor count *physical*
    moves separately from the simulated cloud moves of the placement plan.
    """
    return jax.device_put(x, device), (
        prev_device is not None and prev_device != device
    )


def relayout_rows(
    old_layout: MeshEdgeLayout,
    new_layout: MeshEdgeLayout,
    rows,
    fill,
):
    """Remap ``[..., old.state_width]`` padded device-major rows into
    ``new_layout``'s ``[..., new.state_width]`` shape.

    A pure permutation through global vertex order: real rows land exactly
    once, padding rows carry ``fill`` (the program identity / an empty
    frontier), so the represented global state is bit-identical.
    """
    if old_layout.n_vertices != new_layout.n_vertices:
        raise ValueError(
            f"layouts disagree on n_vertices: {old_layout.n_vertices} vs "
            f"{new_layout.n_vertices}"
        )
    rows = jnp.asarray(rows)
    out = jnp.full(
        rows.shape[:-1] + (new_layout.state_width,), fill, dtype=rows.dtype
    )
    return out.at[..., new_layout.pos_of_vertex].set(
        rows[..., old_layout.pos_of_vertex]
    )


def relayout_state(
    old_layout: MeshEdgeLayout,
    new_layout: MeshEdgeLayout,
    state,
    *,
    identity,
    mesh: Mesh | None = None,
):
    """Remap a carried window state (``dist``/``frontier`` padded shards plus
    the replicated ``n_supersteps`` budget) from ``old_layout`` onto
    ``new_layout``.

    ``state`` is any NamedTuple with ``dist``/``frontier`` leaves in the old
    padded layout (the engine's ``WindowState``); the returned state is the
    same type with both remapped -- exact in global vertex order, see
    ``relayout_rows`` -- and, when ``mesh`` is given, re-committed to the
    partition-axis sharding so each device owns its new shard.  The
    ``A -> B -> A`` round trip is bit-identical by construction.
    """
    dist = relayout_rows(old_layout, new_layout, state.dist, identity)
    frontier = relayout_rows(old_layout, new_layout, state.frontier, False)
    if mesh is not None:
        sh = traversal_state_sharding(mesh)
        dist = jax.device_put(dist, sh)
        frontier = jax.device_put(frontier, sh)
    return state._replace(dist=dist, frontier=frontier)


class MeshTraversalProgram:
    """The shard_map-ed window program for one (graph, mesh) pair.

    Static per-device constant tables (edge shards, wire-slot maps) are
    uploaded once *per layout* with a leading device axis sharded over
    ``parts``; the active layout can be swapped between windows
    (``ensure_layout``) and both the uploaded constants and the jitted window
    programs are LRU-cached so revisiting a layout costs neither a re-upload
    nor a re-jit.
    """

    def __init__(
        self,
        pg: PartitionedGraph,
        mesh: Mesh,
        device_of_part: np.ndarray | None = None,
        program: VertexProgram | None = None,
        *,
        layout_cache_size: int = DEFAULT_LAYOUT_CACHE_SIZE,
        window_cache_size: int = DEFAULT_WINDOW_CACHE_SIZE,
        backend: str = "xla",
        block_n: int = 512,
        block_e: int = 512,
        mirror_degree: int | None = None,
    ):
        d_n = mesh_size(mesh)
        if d_n < 2:
            raise ValueError(
                "MeshTraversalProgram needs >= 2 mesh devices; the engine "
                "uses its dense path for single-device meshes"
            )
        if device_of_part is None:
            device_of_part = contiguous_device_map(pg.n_parts, d_n)
        self.mesh = mesh
        self.pg = pg
        self.program = validate_program(program or SsspProgram())
        self.mirror_degree = mirror_degree
        ml = mesh_edge_layout(
            pg, device_of_part, d_n, mirror_degree=mirror_degree
        )
        # whether the layout actually mirrors is a property of the partition
        # map alone (partition._mirror_hub_plan), so it is stable across
        # relayout swaps -- the signature never changes under ensure_layout
        mirrored = ml.m_pad > 0
        # the engine shape runs exactly one pre-aggregated all_to_all per
        # superstep (two when mirrored: wire exchange + mirror sync) and
        # defers every counter psum to the window epilogue
        # (MESH_WINDOW_EPILOGUE); the declared signature is the same source
        # of truth the jaxpr auditor checks the trace against, so a program
        # declaring a different exchange shape is rejected up front
        self.signature = validate_collective_signature(
            self.program, mirrored=mirrored
        )
        expected_a2a = 2 if mirrored else 1
        if self.signature["all_to_all"] != expected_a2a or self.signature["psum"] != 0:
            raise NotImplementedError(
                f"{self.program.name}: collective_signature() declares "
                f"{self.signature}, but this engine's exchange shape is "
                f"{expected_a2a} all_to_all(s) per superstep with psums only "
                "in the epilogue"
            )
        self.n_parts = pg.n_parts
        validate_backend(backend)
        self.backend = backend
        self._block_n, self._block_e = int(block_n), int(block_e)
        # layout key -> (layout, uploaded device consts); LRU so a replanned
        # run cycling through placements holds a bounded device footprint
        self._layout_states = BoundedCache(layout_cache_size)
        # window_cache_key -> jitted window fn; a swap between shape-identical
        # layouts reuses the same program (consts are args)
        self._windows = BoundedCache(window_cache_size)
        self._activate(ml)

    def _activate(self, ml: MeshEdgeLayout) -> None:
        """Make ``ml`` the active layout, uploading its consts on first use."""

        def build():
            consts_np, statics = build_window_consts(
                self.pg, self.program, ml,
                backend=self.backend,
                block_n=self._block_n, block_e=self._block_e,
            )
            consts = tuple(
                jax.device_put(
                    jnp.asarray(a), per_device_sharding(self.mesh, np.ndim(a))
                )
                for a in consts_np
            )
            return (ml, consts, statics)

        entry = self._layout_states.get_or_build(ml.layout_key, build)
        self.layout, self._consts, self._statics = entry
        self._const_specs = tuple(
            per_device_spec(c.ndim) for c in self._consts
        )

    def ensure_layout(self, state, device_of_part) -> tuple:
        """Swap to the layout for ``device_of_part`` (incrementally rebuilt
        from the active one when possible) and remap the carried ``state``
        into it.  Returns ``(state, swapped)``; a no-op when the map is
        already active."""
        old = self.layout
        ml = mesh_edge_layout(
            self.pg, device_of_part, old.n_devices, base=old,
            mirror_degree=self.mirror_degree,
        )
        if ml is old:
            return state, False
        self._activate(ml)
        state = relayout_state(
            old, ml, state, identity=self.program.identity, mesh=self.mesh
        )
        return state, True

    # -- state layout --------------------------------------------------------

    def init_state(self, sources: np.ndarray) -> tuple[jax.Array, jax.Array]:
        """Sharded padded ``(state, frontier)`` for a batch of sources: the
        program's global-order init scattered into the device-major layout
        (padding rows carry the program identity / an empty frontier)."""
        prog = self.program
        state_g, fr_g = prog.init(self.pg, np.asarray(sources, dtype=np.int64))
        s_batch = state_g.shape[0]
        width = self.layout.state_width
        state = np.full((s_batch, width), prog.identity, dtype=prog.dtype)
        state[:, self.layout.pos_of_vertex] = state_g
        frontier = np.zeros((s_batch, width), dtype=bool)
        frontier[:, self.layout.pos_of_vertex] = fr_g
        sh = traversal_state_sharding(self.mesh)
        return jax.device_put(state, sh), jax.device_put(frontier, sh)

    # -- the device program --------------------------------------------------

    def window(self, dist, frontier, nst0, m_max: int):
        """Run up to ``m_max`` supersteps on the *active* layout; mirrors
        ``_window_impl``'s output tuple ``(dist, frontier, nst, we, wv, ms,
        it, sg, wire, pact, done)`` with ``dist``/``frontier`` in the padded
        sharded layout."""
        return self._window_fn(m_max)(dist, frontier, nst0, *self._consts)

    def _window_fn(self, m_max: int):
        """The jitted window for the active layout, built on first use."""
        key = window_cache_key(self.layout, m_max, self.backend, self._statics)
        return self._windows.get_or_build(key, lambda: self._build(m_max))

    def window_hlo(self, s_batch: int, m_max: int) -> str:
        """The compiled HLO text of ``window`` for a batch of ``s_batch``
        rows on the active layout (``TraversalEngine.window_hlo``)."""
        width = self.layout.state_width
        sh = traversal_state_sharding(self.mesh)
        sds = jax.ShapeDtypeStruct
        state = (
            sds((s_batch, width), self.program.dtype, sharding=sh),
            sds((s_batch, width), np.bool_, sharding=sh),
            sds((s_batch,), np.int32),
        )
        lowered = self._window_fn(m_max).lower(*state, *self._consts)
        return lowered.compile().as_text()

    def _build(self, m_max: int):
        body = window_body(
            self.pg, self.program, self.layout, m_max,
            backend=self.backend, statics=self._statics,
        )
        state = traversal_state_spec()
        rep = P()
        mapped = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(state, state, rep) + self._const_specs,
            out_specs=(state, state, rep, rep, rep, rep, rep, rep, rep, rep, rep),
            check_vma=False,
        )
        return jax.jit(mapped)

    @staticmethod
    def _body(
        dist, frontier, nst0,
        lsrc, ldst, lw, ldeg, lvalid, part_of_pos,
        rsrc, rw, rslot, rdeg, rvalid, recv_idx,
        msrc, mw, mslot, mdeg, mvalid, mrecv_idx,
        *blockmaps,
        m_max: int, n_parts: int, n_pad: int, w_pad: int, d_n: int,
        prog: VertexProgram, n_global: int, m_pad: int = 0,
        backend: str = "xla", statics=None,
    ):
        # per-device blocks arrive with a leading length-1 device axis
        with scope("local", "gather"):
            lsrc, ldst, lw = lsrc[0], ldst[0], lw[0]
            lvalid, part_of_pos = lvalid[0], part_of_pos[0]
        with scope("remote", "gather"):
            rsrc, rw, rslot = rsrc[0], rw[0], rslot[0]
            rvalid, recv_idx = rvalid[0], recv_idx[0]
            msrc, mw, mslot = msrc[0], mw[0], mslot[0]
            mvalid, mrecv_idx = mvalid[0], mrecv_idx[0]
        with scope("local", "counters"):
            ldeg = ldeg[0]
        with scope("remote", "counters"):
            # the remote plane's degree and the mirror plane's count together
            # toward msgs_sent; the unmirrored program never reads mdeg
            rdeg = rdeg[0] + mdeg[0] if m_pad > 0 else rdeg[0]
        s_batch, p = dist.shape[0], n_parts
        ident = prog.identity
        # host-static mirror gate: with no mirror slots the traced program is
        # byte-identical to the unmirrored engine (no cache carry, no second
        # collective, the zero-width mirror constants are dead arguments)
        use_mirror = m_pad > 0
        # monotone programs carry the per-window mirror cache that suppresses
        # unimproved re-sends; stationary apply() needs every superstep's
        # aggregate delivered, so its mirror plane syncs statelessly
        use_cache = use_mirror and not prog.stationary
        seg_red = (
            jax.ops.segment_min if prog.reduce == "min" else jax.ops.segment_sum
        )

        seg_red_l = jax.vmap(
            lambda c: seg_red(
                c, ldst, num_segments=n_pad, indices_are_sorted=True
            )
        )
        seg_red_wire = jax.vmap(
            lambda c: seg_red(
                c, rslot, num_segments=d_n * w_pad, indices_are_sorted=True
            )
        )
        seg_red_mir = jax.vmap(
            lambda c: seg_red(
                c, mslot, num_segments=d_n * m_pad, indices_are_sorted=True
            )
        )

        # kernel backend: the sharded reductions above run as Pallas
        # block-skipping kernels over the per-device static block maps; every
        # other op (counters, scatters, the collectives) stays on XLA
        use_kernel = backend != "xla"
        if use_kernel:
            with scope("local", "relax"):
                lbs, lbc = blockmaps[0][0], blockmaps[1][0]
            with scope("remote", "relax"):
                wbs, wbc = blockmaps[2][0], blockmaps[3][0]
            bn_l, be_l, lt_max, bn_w, be_w, wt_max = statics[:6]
            if use_mirror:
                with scope("remote", "relax"):
                    mbs, mbc = blockmaps[4][0], blockmaps[5][0]
                bn_m, be_m, mt_max = statics[6:]
            interp = backend == "pallas-interpret"

        def relax_l(cand, base=None):
            if use_kernel:
                if base is None:
                    base = jnp.full((cand.shape[0], n_pad), ident, cand.dtype)
                return relax_blockmap_call(
                    lbs, lbc, ldst, cand, base,
                    reduce=prog.reduce, block_n=bn_l, block_e=be_l,
                    t_max=lt_max, interpret=interp,
                )
            r = seg_red_l(cand)
            return r if base is None else prog.combine(base, r)

        def red_wire(cand):
            if use_kernel:
                base = jnp.full(
                    (cand.shape[0], d_n * w_pad), ident, cand.dtype
                )
                return relax_blockmap_call(
                    wbs, wbc, rslot, cand, base,
                    reduce=prog.reduce, block_n=bn_w, block_e=be_w,
                    t_max=wt_max, interpret=interp,
                )
            return seg_red_wire(cand)

        def red_mir(cand, base=None):
            """Combine candidates into mirror slots, folded into ``base``
            (the monotone mirror cache) in one fused kernel pass."""
            if use_kernel:
                if base is None:
                    base = jnp.full(
                        (cand.shape[0], d_n * m_pad), ident, cand.dtype
                    )
                return relax_blockmap_call(
                    mbs, mbc, mslot, cand, base,
                    reduce=prog.reduce, block_n=bn_m, block_e=be_m,
                    t_max=mt_max, interpret=interp,
                )
            r = seg_red_mir(cand)
            return r if base is None else prog.combine(base, r)
        seg_any_wire = jax.vmap(
            lambda v: jax.ops.segment_max(
                v, rslot, num_segments=d_n * w_pad, indices_are_sorted=True
            )
        )
        seg_any_mir = jax.vmap(
            lambda v: jax.ops.segment_max(
                v, mslot, num_segments=d_n * m_pad, indices_are_sorted=True
            )
        )

        def part_sum(x):
            # [S, n_pad] int32 -> [S, P]: one dense reduction over the vertex
            # axis, no scatter.  With x a per-vertex degree under a frontier
            # it is the per-arc count, since every arc counts toward its
            # source's partition.
            in_part = part_of_pos[None, None, :] == jnp.arange(p)[None, :, None]
            return jnp.sum(
                jnp.where(in_part, x[:, None, :], 0), axis=-1, dtype=jnp.int32
            )

        def g_any(flags):  # [S] bool per device -> [S] bool, mesh-global
            with collective():
                return jax.lax.pmax(flags.astype(jnp.int32), PARTS) > 0

        with scope("remote", "relax"):
            recv_flat = recv_idx.reshape(-1)  # [D * w_pad] local dst rows
            mrecv_flat = mrecv_idx.reshape(-1)  # [D * m_pad] local hub rows

        def exchange(src_vals, active_re):
            """Wire aggregation -> one all-to-all -> (recv aggregates [S,
            D*w_pad], wire count [S]).  ``combine``-aggregates per
            destination slot BEFORE the collective for any program."""
            with scope("remote", "gather"):
                cand = jnp.where(active_re, prog.relax(src_vals, rw), ident)
            with scope("remote", "relax"):
                send = red_wire(cand)
            with scope("remote", "counters"):
                if prog.reduce == "min":
                    # a slot is on the wire iff some active edge fed it, which
                    # for min-programs is exactly "the aggregate is not
                    # identity"
                    wire_s = (send != ident).sum(axis=1).astype(jnp.int32)
                else:
                    # a sum can legitimately hit the identity; count fed slots
                    wire_s = (
                        (seg_any_wire(active_re.astype(jnp.int32)) > 0)
                        .sum(axis=1)
                        .astype(jnp.int32)
                    )
            with scope("remote", "collective"):
                recv = jax.lax.all_to_all(
                    send.reshape(s_batch, d_n, w_pad),
                    PARTS, split_axis=1, concat_axis=1, tiled=True,
                )
                return recv.reshape(s_batch, -1), wire_s

        def mirror_sync(send):
            """The second collective: one value per (device, hub) block
            entry, same static-shape tiled all-to-all as the wire plane."""
            with scope("remote", "collective"):
                recv = jax.lax.all_to_all(
                    send.reshape(s_batch, d_n, m_pad),
                    PARTS, split_axis=1, concat_axis=1, tiled=True,
                )
                return recv.reshape(s_batch, -1)

        def upd_counters(s, bufs, rows):
            """Write each superstep-``s`` counter row into its buffer."""
            with scope("remote", "counters"):
                return tuple(
                    jax.lax.dynamic_update_index_in_dim(buf, row, s, axis=1)
                    for buf, row in zip(bufs, rows)
                )

        def stationary_superstep(carry):
            # one gather pass (local + wire), program.apply at the boundary
            s, d, fr, we, wv, ms, it, wire, nst = carry
            with scope("local", "frontier"):
                nst = nst + g_any(fr.any(axis=1)).astype(jnp.int32)

            with scope("local", "gather"):
                active_le = fr[:, lsrc] & lvalid
                cand = jnp.where(active_le, prog.relax(d[:, lsrc], lw), ident)
            with scope("local", "relax"):
                acc = relax_l(cand)
            with scope("local", "counters"):
                we_s = part_sum(jnp.where(fr, ldeg, 0))
                wv_s = part_sum(fr.astype(jnp.int32))
                it_s = g_any(fr.any(axis=1)).astype(jnp.int32)

            with scope("remote", "gather"):
                active_re = fr[:, rsrc] & rvalid
                src_vals = d[:, rsrc]
            recv, wire_s = exchange(src_vals, active_re)
            with scope("remote", "relax"):
                if prog.reduce == "min":
                    acc = acc.at[:, recv_flat].min(recv)
                else:
                    acc = acc.at[:, recv_flat].add(recv)
            with scope("remote", "counters"):
                ms_s = part_sum(jnp.where(fr, rdeg, 0))

            if use_mirror:
                # stateless mirror: combine locally per (owner, hub), sync
                # this superstep's aggregate -- apply() is arbitrary, so no
                # cross-superstep suppression is sound here.  Fed-slot
                # billing matches the wire plane's, so wire_msgs is
                # unchanged vs the unmirrored path.
                with scope("remote", "gather"):
                    active_me = fr[:, msrc] & mvalid
                    mcand = jnp.where(
                        active_me, prog.relax(d[:, msrc], mw), ident
                    )
                with scope("remote", "relax"):
                    msend = red_mir(mcand)
                with scope("remote", "counters"):
                    if prog.reduce == "min":
                        wire_m = (msend != ident).sum(axis=1).astype(jnp.int32)
                    else:
                        wire_m = (
                            (seg_any_mir(active_me.astype(jnp.int32)) > 0)
                            .sum(axis=1)
                            .astype(jnp.int32)
                        )
                mrecv = mirror_sync(msend)
                with scope("remote", "relax"):
                    if prog.reduce == "min":
                        acc = acc.at[:, mrecv_flat].min(mrecv)
                    else:
                        acc = acc.at[:, mrecv_flat].add(mrecv)
                with scope("remote", "counters"):
                    wire_s = wire_s + wire_m

            with scope("remote", "frontier"):
                new_d = prog.apply(d, acc, n_global)
                next_fr = fr & prog.keep_running(nst)[:, None]
                s_next = s + 1

            we, wv, ms, it, wire = upd_counters(
                s, (we, wv, ms, it, wire), (we_s, wv_s, ms_s, it_s, wire_s)
            )
            return s_next, new_d, next_fr, we, wv, ms, it, wire, nst

        def monotone_superstep(carry):
            if use_cache:
                s, d, fr, we, wv, ms, it, wire, nst, mcache = carry
            else:
                s, d, fr, we, wv, ms, it, wire, nst = carry
            with scope("local", "frontier"):
                nst = nst + g_any(fr.any(axis=1)).astype(jnp.int32)

            # -- local closure: same iteration count on every device ----------
            def icond(c):
                with scope("local", "frontier"):
                    return g_any(c[1].any())

            def ibody(c):
                d_i, f_i, we_s, wv_s, it_s, touched = c
                with scope("local", "gather"):
                    active_e = f_i[:, lsrc] & lvalid
                    cand = jnp.where(
                        active_e, prog.relax(d_i[:, lsrc], lw), ident
                    )
                with scope("local", "relax"):
                    new_d = relax_l(cand, d_i)
                with scope("local", "frontier"):
                    improved = prog.is_active(new_d, d_i)
                    touched = touched | improved
                with scope("local", "counters"):
                    we_s = we_s + part_sum(jnp.where(f_i, ldeg, 0))
                    wv_s = wv_s + part_sum(f_i.astype(jnp.int32))
                    it_s = it_s + g_any(f_i.any(axis=1)).astype(jnp.int32)
                return new_d, improved, we_s, wv_s, it_s, touched

            with scope("local", "counters"):
                z_p = jnp.zeros((s_batch, p), jnp.int32)
                z_s = jnp.zeros((s_batch,), jnp.int32)
            d2, _, we_s, wv_s, it_s, touched = jax.lax.while_loop(
                icond, ibody, (d, fr, z_p, z_p, z_s, fr)
            )

            # -- exchange: aggregate per destination, then ONE all-to-all -----
            with scope("remote", "gather"):
                active_re = touched[:, rsrc] & rvalid
                src_vals = d2[:, rsrc]
            recv, wire_s = exchange(src_vals, active_re)
            with scope("remote", "relax"):
                new_d = d2.at[:, recv_flat].min(recv)
            with scope("remote", "counters"):
                ms_s = part_sum(jnp.where(touched, rdeg, 0))

            if use_cache:
                # -- mirror sync: combine into the window-local cache, send
                # only slots whose best-ever value improved.  Exact for
                # min-programs: an unimproved candidate is >= the cache,
                # which was synced the superstep it last improved, so the
                # owner already holds a value <= it (module docstring).
                with scope("remote", "gather"):
                    active_me = touched[:, msrc] & mvalid
                    mcand = jnp.where(
                        active_me, prog.relax(d2[:, msrc], mw), ident
                    )
                with scope("remote", "relax"):
                    new_mc = red_mir(mcand, mcache)
                    improved_m = prog.is_active(new_mc, mcache)
                    msend = jnp.where(improved_m, new_mc, ident)
                with scope("remote", "counters"):
                    wire_m = (msend != ident).sum(axis=1).astype(jnp.int32)
                mrecv = mirror_sync(msend)
                with scope("remote", "relax"):
                    new_d = new_d.at[:, mrecv_flat].min(mrecv)
                with scope("remote", "counters"):
                    wire_s = wire_s + wire_m

            with scope("remote", "frontier"):
                next_fr = prog.is_active(new_d, d2)
                s_next = s + 1

            we, wv, ms, it, wire = upd_counters(
                s, (we, wv, ms, it, wire), (we_s, wv_s, ms_s, it_s, wire_s)
            )
            out = (s_next, new_d, next_fr, we, wv, ms, it, wire, nst)
            if use_cache:
                out = out + (new_mc,)
            return out

        superstep_body = (
            stationary_superstep if prog.stationary else monotone_superstep
        )

        def superstep_cond(carry):
            s, _, fr, *_ = carry
            with scope("remote", "frontier"):
                return (s < m_max) & g_any(fr.any())

        with scope("local", "counters"):
            zeros_smp = jnp.zeros((s_batch, m_max, p), jnp.int32)
            zeros_sm = jnp.zeros((s_batch, m_max), jnp.int32)
            init = (
                jnp.int32(0), dist, frontier,
                zeros_smp, zeros_smp, zeros_smp, zeros_sm, zeros_sm, nst0,
            )
        if use_cache:
            # the mirror cache is window-local: it starts at identity each
            # window, so the first improvement after a window boundary (or a
            # relayout swap, which happens only between windows) re-syncs --
            # a harmless duplicate send, never a missed one
            with scope("remote", "relax"):
                init = init + (
                    jnp.full((s_batch, d_n * m_pad), ident, dist.dtype),
                )
        final = jax.lax.while_loop(superstep_cond, superstep_body, init)
        _, d, fr, we, wv, ms, it, wire, nst = final[:9]
        # partitions never span devices: the psum of disjoint partial
        # counters reconstructs the exact global integers
        with scope(FINISH):
            with collective():
                we = jax.lax.psum(we, PARTS)
                wv = jax.lax.psum(wv, PARTS)
                ms = jax.lax.psum(ms, PARTS)
                wire = jax.lax.psum(wire, PARTS)
                pact = jax.lax.psum(part_sum(fr.astype(jnp.int32)), PARTS) > 0
            done = ~g_any(fr.any(axis=1))
            sg = jnp.zeros((s_batch, m_max, 0), bool)  # mesh: single-device-only
        return d, fr, nst, we, wv, ms, it, sg, wire, pact, done
