"""Graph partitioners and the partition-aware static edge layout.

The paper partitions with METIS (vertex-balanced, load factor 1.03, minimal
edge cut).  METIS is unavailable offline; ``bfs_grow_partition`` is a
multi-seed region-growing partitioner with a greedy boundary-refinement pass
that achieves the same *qualitative* regime: balanced vertex counts and
well-connected partitions (few, large subgraphs per partition).
``hash_partition`` reproduces Giraph's default (balanced but high cut).

``partitioned_edge_layout`` turns a ``PartitionedGraph`` into the static
CSR layout the device-resident traversal engine runs on: local and remote
edges split into two dst-sorted ``CsrEdgeLayout``s (so the inner closure
loop scans only local edges and the superstep-boundary exchange only remote
ones, with no per-edge ``is_local`` masking), each carrying the per-edge src
partition ids needed for the paper's work counters.  Built once per graph
and cached on the ``PartitionedGraph`` instance.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.graph.structs import (
    _BLOCK_CACHE_MAX,
    BoundedCache,
    CsrEdgeLayout,
    Graph,
    MeshEdgeLayout,
    PartitionedGraph,
    device_block_ranges,
    dst_sorted_layout,
    mesh_layout_key,
)
from repro.spans import span


@dataclasses.dataclass(frozen=True)
class PartitionedEdgeLayout:
    """Static traversal layout: dst-sorted local + remote edge sets.

    ``local_eid``/``remote_eid`` map each layout row back to the original
    edge-list index, so a per-program ``[E]`` edge-weight plane
    (``graph.program.VertexProgram.edge_plane``) permutes into layout order
    with one gather instead of a rebuild.
    """

    local: CsrEdgeLayout  # within-partition edges, dst ascending
    remote: CsrEdgeLayout  # cross-partition edges, dst ascending
    local_part: np.ndarray  # [E_local] int32 partition of each local edge
    remote_src_part: np.ndarray  # [E_remote] int32 src partition per remote edge
    local_eid: np.ndarray  # [E_local] int64 original edge index per local row
    remote_eid: np.ndarray  # [E_remote] int64 original edge index per remote row


def partitioned_edge_layout(pg: PartitionedGraph) -> PartitionedEdgeLayout:
    """The static edge layout for ``pg`` (cached on the instance)."""
    cached = pg.__dict__.get("_edge_layout")
    if cached is not None:
        return cached
    g = pg.graph
    with span("graph.layout"):
        local = pg.is_local_edge
        w = g.edge_weights
        part = pg.part_of_vertex.astype(np.int32)
        with span("graph.layout.local"):
            loc = dst_sorted_layout(
                g.n_vertices, g.src[local], g.dst[local], w[local]
            )
            local_part = part[loc.src]
            local_eid = np.flatnonzero(local)[loc.perm]
        with span("graph.layout.remote"):
            rem = dst_sorted_layout(
                g.n_vertices, g.src[~local], g.dst[~local], w[~local]
            )
            remote_src_part = part[rem.src]
            remote_eid = np.flatnonzero(~local)[rem.perm]
    layout = PartitionedEdgeLayout(
        local=loc,
        remote=rem,
        local_part=local_part,
        remote_src_part=remote_src_part,
        local_eid=local_eid,
        remote_eid=remote_eid,
    )
    pg.__dict__["_edge_layout"] = layout
    return layout


def contiguous_device_map(n_parts: int, n_devices: int) -> np.ndarray:
    """Balanced static partition -> device assignment (contiguous blocks).

    Partition ``i`` goes to device ``i * n_devices // n_parts`` when
    ``n_parts >= n_devices`` (blocks differ by at most one partition); with
    more devices than partitions the first ``n_parts`` devices get one
    partition each and the rest stay empty -- a legal, if wasteful, mesh.
    """
    if n_parts <= 0 or n_devices <= 0:
        raise ValueError(f"need positive sizes, got P={n_parts} D={n_devices}")
    if n_parts >= n_devices:
        return (np.arange(n_parts, dtype=np.int64) * n_devices // n_parts).astype(
            np.int32
        )
    return np.arange(n_parts, dtype=np.int32)


#: layouts retained per (PartitionedGraph, canonical key); replanned runs can
#: visit many device maps, so the cache is LRU-bounded rather than unbounded
_LAYOUT_CACHE_MAX = 16

#: incremental-rebuild bases retained per (device count, mirror knob) (one
#: mesh width is the common case; a handful covers elastic sweeps)
_LAST_BASE_CACHE_MAX = 4

#: hub plans retained per (pg, mirror_degree); a run uses one threshold, a
#: mirror sweep a handful
_HUB_PLAN_CACHE_MAX = 8


def _mirror_hub_plan(
    pg: PartitionedGraph, mirror_degree: int | None
) -> tuple[np.ndarray, np.ndarray]:
    """(hub_edge [E_remote] bool, nr_hub [P] int64) for a degree threshold.

    A *hub* is a vertex whose cross-partition in-degree (count of remote
    edges targeting it) meets ``mirror_degree``.  The predicate depends only
    on the partition map -- never on the device map -- so the hub set (and
    with it the mirrored collective signature) is stable across elastic
    relayout swaps.  ``mirror_degree=None`` selects no hubs.
    """
    cache = pg.__dict__.get("_mirror_hub_plans")
    if not isinstance(cache, BoundedCache):
        cache = BoundedCache(_HUB_PLAN_CACHE_MAX)
        pg.__dict__["_mirror_hub_plans"] = cache

    def build():
        layout = partitioned_edge_layout(pg)
        if mirror_degree is None:
            hub_edge = np.zeros(layout.remote.n_edges, dtype=bool)
        else:
            indeg = np.bincount(
                layout.remote.dst, minlength=pg.graph.n_vertices
            )
            hub_edge = indeg[layout.remote.dst] >= int(mirror_degree)
        nr_hub = np.bincount(
            layout.remote_src_part[hub_edge], minlength=pg.n_parts
        ).astype(np.int64)
        return hub_edge, nr_hub

    key = None if mirror_degree is None else int(mirror_degree)
    return cache.get_or_build(key, build)


@dataclasses.dataclass(frozen=True)
class _PartSlices:
    """Per-partition views into the static partition layout, built once per
    graph and reused by every mesh-layout (re)build.

    All selections preserve the global dst-ascending order of the underlying
    ``PartitionedEdgeLayout``, so a per-device edge list assembled as
    ``sort(concat(slices of its partitions))`` is *identical* to the
    ``flatnonzero`` scan over the full edge set -- incremental rebuilds
    produce byte-identical layouts.
    """

    verts: list  # [P] ascending vertex ids per partition
    lsel: list  # [P] indices into layout.local, dst-ascending
    rsel: list  # [P] indices into layout.remote, dst-ascending
    nv: np.ndarray  # [P] vertex counts
    nl: np.ndarray  # [P] local-edge counts
    nr: np.ndarray  # [P] remote out-edge counts
    rdst_part: np.ndarray  # [E_remote] partition of each remote edge's dst
    reach: np.ndarray  # [P, P] bool: partition i has a remote edge into j


def _group_by(labels: np.ndarray, n_groups: int) -> list:
    """[n_groups] ascending index arrays, one per label value (stable)."""
    order = np.argsort(labels, kind="stable")
    counts = np.bincount(labels, minlength=n_groups)
    return np.split(order, np.cumsum(counts)[:-1])


def _mesh_part_slices(pg: PartitionedGraph) -> _PartSlices:
    cached = pg.__dict__.get("_mesh_part_slices")
    if cached is not None:
        return cached
    layout = partitioned_edge_layout(pg)
    p = pg.n_parts
    part = pg.part_of_vertex.astype(np.int64)
    rdst_part = part[layout.remote.dst].astype(np.int32)
    reach = np.zeros((p, p), dtype=bool)
    reach[layout.remote_src_part, rdst_part] = True
    slices = _PartSlices(
        verts=_group_by(part, p),
        lsel=_group_by(layout.local_part.astype(np.int64), p),
        rsel=_group_by(layout.remote_src_part.astype(np.int64), p),
        nv=np.bincount(part, minlength=p),
        nl=np.bincount(layout.local_part, minlength=p),
        nr=np.bincount(layout.remote_src_part, minlength=p),
        rdst_part=rdst_part,
        reach=reach,
    )
    pg.__dict__["_mesh_part_slices"] = slices
    return slices


#: sentinel: pick the most recently built layout for this (pg, D) as the
#: incremental base (None forces a from-scratch build)
_AUTO_BASE = object()


def mesh_edge_layout(
    pg: PartitionedGraph,
    device_of_part: np.ndarray,
    n_devices: int,
    *,
    base: MeshEdgeLayout | None | object = _AUTO_BASE,
    mirror_degree: int | None = None,
    changed_devices: np.ndarray | None = None,
) -> MeshEdgeLayout:
    """Build the static mesh-aware layout for a fixed partition -> device map.

    Host-side numpy, cached per ``(pg, mesh_layout_key(...), mirror_degree)``
    (LRU-bounded: dynamic re-layout visits a map per replan).  See
    ``structs.MeshEdgeLayout`` for the contract; the key invariants preserved
    from the single-device layout are (a) per-device local ``dst`` rows stay
    ascending (a device-filtered subsequence of the globally dst-sorted local
    edges, renumbered by a per-device monotone map), and (b) per-device
    remote edges are ``(dst_device, dst_vertex)``-sorted so wire-slot ids
    ascend too -- every segment reduction keeps the ``indices_are_sorted``
    fast path.

    ``mirror_degree`` selects hub destinations (``_mirror_hub_plan``) whose
    incoming remote edges move to the structurally identical *mirror* plane
    (``msrc``/``mslot``/... with ``m_pad`` slots per block); ``None`` (the
    default) and zero-hub graphs build layouts whose pre-existing fields are
    byte-identical to an unmirrored build, with zero-width mirror arrays.

    **Incremental rebuild** (the dynamic re-layout hot path): when ``base`` is
    a previously built layout for the same ``(pg, n_devices)`` (the default
    picks the most recent one), only the per-device blocks the map change
    actually touches are recomputed from the cached per-partition slices
    (``_mesh_part_slices``):

      * vertex/local-edge blocks of devices whose partition set changed,
      * remote/wire blocks of src devices that are changed themselves OR send
        into any partition hosted on a changed device (their
        ``(dst_device, dst_vertex)`` sort and receive rows shift),

    everything else is copied from ``base``.  If any pad shape
    (``n_pad``/``e_local_pad``/``e_remote_pad``/``w_pad``) differs, the build
    degrades to from-scratch -- reuse is only valid shape-stable.  Either
    path produces the byte-identical canonical layout; the chosen path is
    recorded in ``layout.__dict__['_build_info']``.
    """
    device_of_part = np.asarray(device_of_part, dtype=np.int32)
    if device_of_part.shape != (pg.n_parts,):
        raise ValueError(
            f"device_of_part has shape {device_of_part.shape}, "
            f"expected ({pg.n_parts},)"
        )
    if device_of_part.min() < 0 or device_of_part.max() >= n_devices:
        raise ValueError(
            f"device ids must lie in [0, {n_devices}), got "
            f"[{device_of_part.min()}, {device_of_part.max()}]"
        )
    if mirror_degree is not None:
        mirror_degree = int(mirror_degree)
        if mirror_degree < 1:
            raise ValueError(
                f"mirror_degree must be >= 1 or None, got {mirror_degree}"
            )
    cache = pg.__dict__.get("_mesh_layouts")
    if not isinstance(cache, BoundedCache):
        cache = BoundedCache(_LAYOUT_CACHE_MAX)
        pg.__dict__["_mesh_layouts"] = cache
    generation = int(pg.__dict__.get("_delta_generation", 0))
    key = mesh_layout_key(device_of_part, n_devices, generation) + (
        mirror_degree,
    )
    if key in cache:
        cache.move_to_end(key)
        return cache[key]
    last = pg.__dict__.get("_mesh_layout_last")
    if not isinstance(last, BoundedCache):
        last = BoundedCache(_LAST_BASE_CACHE_MAX)
        pg.__dict__["_mesh_layout_last"] = last
    last_key = (int(n_devices), mirror_degree)
    if base is _AUTO_BASE:
        base = last.get(last_key)
    if base is not None and (
        base.n_devices != int(n_devices)
        or base.n_parts != pg.n_parts
        or base.n_vertices != pg.graph.n_vertices
        or base.mirror_degree != mirror_degree
    ):
        base = None
    if base is not None and base.delta_generation != generation:
        # Cross-generation reuse (the delta-merge seam) is only sound when the
        # caller names the devices whose edge content changed; without the
        # mask the map-diff detection below would wrongly copy stale blocks.
        if changed_devices is None:
            base = None

    out = _build_mesh_layout(
        pg, device_of_part, int(n_devices), base, mirror_degree,
        changed_devices=changed_devices,
    )
    cache.put(key, out)
    last.put(last_key, out)
    return out


def _build_mesh_layout(
    pg: PartitionedGraph,
    device_of_part: np.ndarray,
    d_n: int,
    base: MeshEdgeLayout | None,
    mirror_degree: int | None = None,
    changed_devices: np.ndarray | None = None,
) -> MeshEdgeLayout:
    layout = partitioned_edge_layout(pg)
    slices = _mesh_part_slices(pg)
    n = pg.graph.n_vertices
    parts_of_dev = _group_by(device_of_part.astype(np.int64), d_n)
    dev_of_vertex = device_of_part[pg.part_of_vertex]
    hub_edge, nr_hub = _mirror_hub_plan(pg, mirror_degree)

    # pad shapes from the cached per-partition counts (O(P), no edge scans)
    nv_dev = np.array([slices.nv[q].sum() for q in parts_of_dev])
    nl_dev = np.array([slices.nl[q].sum() for q in parts_of_dev])
    nr_wire = slices.nr - nr_hub
    nr_dev = np.array([nr_wire[q].sum() for q in parts_of_dev])
    nm_dev = np.array([nr_hub[q].sum() for q in parts_of_dev])
    n_pad = max(1, int(nv_dev.max()))
    e_local_pad = max(1, int(nl_dev.max()))
    e_remote_pad = max(1, int(nr_dev.max()))
    e_mirror_pad = int(nm_dev.max())

    # -- which devices must be rebuilt ---------------------------------------
    all_devs = np.ones(d_n, dtype=bool)
    if base is None or (n_pad, e_local_pad, e_remote_pad, e_mirror_pad) != (
        base.n_pad, base.e_local_pad, base.e_remote_pad, base.e_mirror_pad
    ):
        vert_aff = src_aff = all_devs
        base = None
    else:
        moved = np.flatnonzero(base.device_of_part != device_of_part)
        changed = np.zeros(d_n, dtype=bool)
        changed[base.device_of_part[moved]] = True
        changed[device_of_part[moved]] = True
        if changed_devices is not None:
            # delta-merge seam: devices whose *edge content* changed under an
            # unchanged map (graph.deltas computes the exact set per plane)
            changed |= np.asarray(changed_devices, dtype=bool)
        vert_aff = changed
        # parts whose device-local rows may have shifted = parts hosted on a
        # changed device; src devices reaching any of them re-sort and re-slot
        j_shift = changed[device_of_part]  # [P] bool
        sends_into_shifted = slices.reach[:, j_shift].any(axis=1)  # [P]
        src_aff = changed.copy()
        for d in range(d_n):
            if not src_aff[d] and sends_into_shifted[parts_of_dev[d]].any():
                src_aff[d] = True

    # -- vertex plane: device-major permutation ------------------------------
    if base is None:
        pos_of_vertex = np.empty(n, dtype=np.int64)
        vertex_of_pos = np.full(d_n * n_pad, -1, dtype=np.int64)
        part_of_pos = np.zeros((d_n, n_pad), dtype=np.int32)
        pos_valid = np.zeros((d_n, n_pad), dtype=bool)
    else:
        pos_of_vertex = base.pos_of_vertex.copy()
        vertex_of_pos = base.vertex_of_pos.copy()
        part_of_pos = base.part_of_pos.copy()
        pos_valid = base.pos_valid.copy()
    def _dev_sel(groups: list, d: int) -> np.ndarray:
        """Ascending union of the device's per-partition index slices --
        identical to the full ``flatnonzero`` scan of the scratch build."""
        if not parts_of_dev[d].size:
            return np.empty(0, np.int64)
        return np.sort(np.concatenate([groups[i] for i in parts_of_dev[d]]))

    for d in np.flatnonzero(vert_aff):
        verts = _dev_sel(slices.verts, d)
        pos_of_vertex[verts] = d * n_pad + np.arange(verts.size)
        vertex_of_pos[d * n_pad : d * n_pad + verts.size] = verts
        vertex_of_pos[d * n_pad + verts.size : (d + 1) * n_pad] = -1
        part_of_pos[d] = 0
        part_of_pos[d, : verts.size] = pg.part_of_vertex[verts]
        pos_valid[d] = False
        pos_valid[d, : verts.size] = True

    # -- local edges: filter per device, renumber to device-local rows -------
    loc = layout.local
    if base is None:
        lsrc = np.zeros((d_n, e_local_pad), dtype=np.int32)
        ldst = np.full((d_n, e_local_pad), n_pad - 1, dtype=np.int32)
        lw = np.zeros((d_n, e_local_pad), dtype=np.float32)
        lvalid = np.zeros((d_n, e_local_pad), dtype=bool)
        l_eid = np.zeros((d_n, e_local_pad), dtype=np.int64)
    else:
        lsrc = base.lsrc.copy()
        ldst = base.ldst.copy()
        lw = base.lw.copy()
        lvalid = base.lvalid.copy()
        l_eid = base.l_eid.copy()
    for d in np.flatnonzero(vert_aff):
        sel = _dev_sel(slices.lsel, d)  # ascending rows == global dst order
        m = sel.size
        lsrc[d] = 0
        ldst[d] = n_pad - 1
        lw[d] = 0.0
        lvalid[d] = False
        l_eid[d] = 0
        lsrc[d, :m] = pos_of_vertex[loc.src[sel]] - d * n_pad
        ldst[d, :m] = pos_of_vertex[loc.dst[sel]] - d * n_pad
        lw[d, :m] = loc.weights[sel]
        lvalid[d, :m] = True
        l_eid[d, :m] = sel
        # padding dst rows keep the allocation value n_pad - 1, >= any real
        # local row, so the ascending (indices_are_sorted) contract holds

    # -- remote edges: (src_device, dst_device) blocks + wire slots ----------
    # with mirroring, hub-targeting remote edges leave the wire plane for the
    # structurally identical mirror plane (one slot per (owner_device, hub))
    rem = layout.remote
    ddev = dev_of_vertex[rem.dst]
    remote_block_edges = np.zeros((d_n, d_n), dtype=np.int64)
    wire_slots = np.zeros((d_n, d_n), dtype=np.int64)
    mirror_block_edges = np.zeros((d_n, d_n), dtype=np.int64)
    mirror_slots = np.zeros((d_n, d_n), dtype=np.int64)
    if base is not None:
        keep = ~src_aff
        remote_block_edges[keep] = base.remote_block_edges[keep]
        wire_slots[keep] = base.wire_slots[keep]
        mirror_block_edges[keep] = base.mirror_block_edges[keep]
        mirror_slots[keep] = base.mirror_slots[keep]
    # first pass: per-block raw and distinct-dst counts fix the pad shapes
    per_dev: dict[int, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
    per_dev_m: dict[int, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}

    def _plane_pass(sel: np.ndarray, blocks: np.ndarray, slots: np.ndarray):
        order = np.lexsort((rem.dst[sel], ddev[sel]))
        sel = sel[order]  # (dst_device, dst_vertex)-sorted
        bd = ddev[sel]
        key_dd = bd.astype(np.int64) * n + rem.dst[sel]
        uniq, inv = (
            np.unique(key_dd, return_inverse=True)
            if sel.size
            else (np.empty(0, np.int64), np.empty(0, np.int64))
        )
        blocks[:] = 0
        np.add.at(blocks, bd, 1)
        u_dd = (uniq // n).astype(np.int64)
        slots[:] = 0
        np.add.at(slots, u_dd, 1)
        return (sel, uniq, inv)

    def _first_pass(devs: np.ndarray) -> None:
        for d in devs:
            sel = _dev_sel(slices.rsel, d)
            hub = hub_edge[sel]
            per_dev[int(d)] = _plane_pass(
                sel[~hub], remote_block_edges[d], wire_slots[d]
            )
            per_dev_m[int(d)] = _plane_pass(
                sel[hub], mirror_block_edges[d], mirror_slots[d]
            )

    _first_pass(np.flatnonzero(src_aff))
    w_pad = max(1, int(wire_slots.max()))
    m_pad = int(mirror_slots.max())
    if base is not None and (w_pad != base.w_pad or m_pad != base.m_pad):
        # slot encoding (dd * pad + rank) is global: a w_pad / m_pad change
        # invalidates every block -- degrade to the from-scratch path
        base = None
        vert_aff = src_aff = all_devs
        _first_pass(np.flatnonzero(~np.isin(np.arange(d_n), list(per_dev))))

    rebuilt = np.flatnonzero(src_aff | vert_aff)
    if base is None:
        rsrc = np.zeros((d_n, e_remote_pad), dtype=np.int32)
        rw = np.zeros((d_n, e_remote_pad), dtype=np.float32)
        rslot = np.full((d_n, e_remote_pad), d_n * w_pad - 1, dtype=np.int32)
        rvalid = np.zeros((d_n, e_remote_pad), dtype=bool)
        r_eid = np.zeros((d_n, e_remote_pad), dtype=np.int64)
        recv_idx = np.zeros((d_n, d_n, w_pad), dtype=np.int32)
        msrc = np.zeros((d_n, e_mirror_pad), dtype=np.int32)
        mw = np.zeros((d_n, e_mirror_pad), dtype=np.float32)
        mslot = np.full(
            (d_n, e_mirror_pad), max(0, d_n * m_pad - 1), dtype=np.int32
        )
        mvalid = np.zeros((d_n, e_mirror_pad), dtype=bool)
        m_eid = np.zeros((d_n, e_mirror_pad), dtype=np.int64)
        mrecv_idx = np.zeros((d_n, d_n, m_pad), dtype=np.int32)
    else:
        rsrc = base.rsrc.copy()
        rw = base.rw.copy()
        rslot = base.rslot.copy()
        rvalid = base.rvalid.copy()
        r_eid = base.r_eid.copy()
        recv_idx = base.recv_idx.copy()
        msrc = base.msrc.copy()
        mw = base.mw.copy()
        mslot = base.mslot.copy()
        mvalid = base.mvalid.copy()
        m_eid = base.m_eid.copy()
        mrecv_idx = base.mrecv_idx.copy()
    for d in np.flatnonzero(src_aff):
        sel, uniq, inv = per_dev[int(d)]
        m = sel.size
        rsrc[d] = 0
        rw[d] = 0.0
        rslot[d] = d_n * w_pad - 1
        rvalid[d] = False
        r_eid[d] = 0
        recv_idx[:, d, :] = 0
        if m:
            u_dd = (uniq // n).astype(np.int64)
            u_dst = (uniq % n).astype(np.int64)
            # slot rank within each dst-device group (uniq is (dd, dst)-sorted)
            first_of_dd = np.searchsorted(u_dd, np.arange(d_n))
            slot_of_uniq = np.arange(uniq.size) - first_of_dd[u_dd]
            rsrc[d, :m] = pos_of_vertex[rem.src[sel]] - d * n_pad
            rw[d, :m] = rem.weights[sel]
            rslot[d, :m] = (u_dd[inv] * w_pad + slot_of_uniq[inv]).astype(np.int32)
            rvalid[d, :m] = True
            r_eid[d, :m] = sel
            # receive side: block (d -> dd) slot s lands on the dst vertex's
            # device-local row on device dd
            recv_idx[u_dd, d, slot_of_uniq] = (
                pos_of_vertex[u_dst] - u_dd * n_pad
            ).astype(np.int32)
        # mirror plane: same construction over the hub-targeting edges, with
        # mirror slots in place of wire slots
        sel, uniq, inv = per_dev_m[int(d)]
        m = sel.size
        msrc[d] = 0
        mw[d] = 0.0
        mslot[d] = max(0, d_n * m_pad - 1)
        mvalid[d] = False
        m_eid[d] = 0
        mrecv_idx[:, d, :] = 0
        if m:
            u_dd = (uniq // n).astype(np.int64)
            u_dst = (uniq % n).astype(np.int64)
            first_of_dd = np.searchsorted(u_dd, np.arange(d_n))
            slot_of_uniq = np.arange(uniq.size) - first_of_dd[u_dd]
            msrc[d, :m] = pos_of_vertex[rem.src[sel]] - d * n_pad
            mw[d, :m] = rem.weights[sel]
            mslot[d, :m] = (u_dd[inv] * m_pad + slot_of_uniq[inv]).astype(np.int32)
            mvalid[d, :m] = True
            m_eid[d, :m] = sel
            mrecv_idx[u_dd, d, slot_of_uniq] = (
                pos_of_vertex[u_dst] - u_dd * n_pad
            ).astype(np.int32)

    out = MeshEdgeLayout(
        n_devices=d_n,
        n_vertices=n,
        n_parts=pg.n_parts,
        device_of_part=device_of_part,
        n_pad=n_pad,
        pos_of_vertex=pos_of_vertex,
        vertex_of_pos=vertex_of_pos,
        part_of_pos=part_of_pos,
        pos_valid=pos_valid,
        e_local_pad=e_local_pad,
        lsrc=lsrc,
        ldst=ldst,
        lw=lw,
        lvalid=lvalid,
        l_eid=l_eid,
        e_remote_pad=e_remote_pad,
        w_pad=w_pad,
        rsrc=rsrc,
        rw=rw,
        rslot=rslot,
        rvalid=rvalid,
        r_eid=r_eid,
        recv_idx=recv_idx,
        wire_slots=wire_slots,
        remote_block_edges=remote_block_edges,
        mirror_degree=mirror_degree,
        e_mirror_pad=e_mirror_pad,
        m_pad=m_pad,
        msrc=msrc,
        mw=mw,
        mslot=mslot,
        mvalid=mvalid,
        m_eid=m_eid,
        mrecv_idx=mrecv_idx,
        mirror_slots=mirror_slots,
        mirror_block_edges=mirror_block_edges,
        delta_generation=int(pg.__dict__.get("_delta_generation", 0)),
    )
    out.__dict__["_build_info"] = {
        "incremental": base is not None,
        "devices_rebuilt": int(rebuilt.size),
        "devices_total": d_n,
    }
    if base is not None:
        # carry the Pallas kernel block maps (structs.MeshEdgeLayout.
        # local_block_map / wire_block_map) the same way the edge arrays are
        # carried: recompute only the rows of devices whose edges were
        # rebuilt, copy the rest.  Shapes are stable here by construction
        # (any pad change degraded to base=None above).
        carried = BoundedCache(_BLOCK_CACHE_MAX)
        for key, (bstart, bcnt, _) in (base.__dict__.get("_block_maps") or {}).items():
            kind, bn, be = key
            if kind == "local":
                aff, edge_rows, valid, nseg = vert_aff, ldst, lvalid, n_pad
            elif kind == "mirror":
                aff, edge_rows, valid, nseg = src_aff, mslot, mvalid, d_n * m_pad
            else:
                aff, edge_rows, valid, nseg = src_aff, rslot, rvalid, d_n * w_pad
            start = bstart.copy()
            cnt = bcnt.copy()
            for d in np.flatnonzero(aff):
                start[d], cnt[d], _ = device_block_ranges(
                    edge_rows[d], valid[d], nseg, bn, be
                )
            carried[key] = (start, cnt, max(1, int(cnt.max())))
        if carried:
            out.__dict__["_block_maps"] = carried
    return out


def hash_partition(g: Graph, n_parts: int, *, seed: int = 0) -> PartitionedGraph:
    """Giraph-style hashed placement: balanced vertices, terrible edge cut."""
    mix = np.arange(g.n_vertices, dtype=np.int64) * np.int64(2654435761) + seed
    part = ((mix >> 16) % n_parts).astype(np.int32)
    return PartitionedGraph(g, n_parts, part)


def bfs_grow_partition(
    g: Graph,
    n_parts: int,
    *,
    seed: int = 0,
    balance: float = 1.03,
    refine_sweeps: int = 2,
) -> PartitionedGraph:
    """Multi-seed BFS region growing + greedy cut refinement.

    1. Pick ``n_parts`` seeds spread apart (iterative farthest-first on hops).
    2. Round-robin frontier expansion; each region claims unassigned neighbors
       until it reaches the balance cap ceil(balance * n/k).
    3. ``refine_sweeps`` passes move boundary vertices to the neighboring
       partition holding the majority of their edges when balance permits.
    """
    rng = np.random.default_rng(seed)
    n, k = g.n_vertices, n_parts
    cap = int(np.ceil(balance * n / k))
    with span("graph.partition"):
        with span("graph.partition.seeds"):
            row_ptr, col, _ = g.csr
            seeds = _farthest_seeds(row_ptr, col, n, k, rng)
        with span("graph.partition.grow"):
            part = _grow_regions(row_ptr, col, n, k, cap, seeds)
        with span("graph.partition.refine"):
            for _ in range(refine_sweeps):
                part = _refine_once(g, part, k, cap)

    return PartitionedGraph(g, k, part)


def _farthest_seeds(row_ptr, col, n: int, k: int, rng) -> list:
    """``k`` seeds spread apart: iterative farthest-first on hops."""
    seeds = [int(rng.integers(n))]
    dist = _bfs_hops(row_ptr, col, n, seeds[0])
    for _ in range(k - 1):
        cand = int(np.argmax(np.where(np.isfinite(dist), dist, -1.0)))
        seeds.append(cand)
        dist = np.minimum(dist, _bfs_hops(row_ptr, col, n, cand))
    return seeds


def _grow_regions(row_ptr, col, n: int, k: int, cap: int, seeds) -> np.ndarray:
    """Round-robin frontier expansion from ``seeds``; each region claims
    unassigned neighbors until it reaches ``cap``."""
    part = np.full(n, -1, dtype=np.int32)
    sizes = np.zeros(k, dtype=np.int64)
    frontiers: list[np.ndarray] = []
    for p, s in enumerate(seeds):
        if part[s] == -1:
            part[s] = p
            sizes[p] += 1
        frontiers.append(np.array([s], dtype=np.int64))

    # --- round-robin growth -------------------------------------------------
    while (part == -1).any():
        grew = False
        for p in range(k):
            if sizes[p] >= cap or frontiers[p].size == 0:
                continue
            f = frontiers[p]
            nbrs = _neighbors_of(row_ptr, col, f)
            nbrs = nbrs[part[nbrs] == -1]
            if nbrs.size == 0:
                frontiers[p] = np.array([], dtype=np.int64)
                continue
            nbrs = np.unique(nbrs)
            room = cap - sizes[p]
            if nbrs.size > room:
                nbrs = nbrs[:room]
            part[nbrs] = p
            sizes[p] += nbrs.size
            frontiers[p] = nbrs
            grew = True
        if not grew:
            # disconnected leftovers or all regions full: assign remaining to
            # smallest partitions round-robin
            rest = np.flatnonzero(part == -1)
            order = np.argsort(sizes)
            for i, v in enumerate(rest):
                p = int(order[i % k])
                part[v] = p
                sizes[p] += 1
            break
    return part


def _bfs_hops(row_ptr: np.ndarray, col: np.ndarray, n: int, source: int) -> np.ndarray:
    dist = np.full(n, np.inf)
    dist[source] = 0
    frontier = np.array([source], dtype=np.int64)
    d = 0
    while frontier.size:
        d += 1
        nbrs = _neighbors_of(row_ptr, col, frontier)
        nbrs = np.unique(nbrs[~np.isfinite(dist[nbrs])])
        dist[nbrs] = d
        frontier = nbrs
    return dist


def _neighbors_of(row_ptr: np.ndarray, col: np.ndarray, vs: np.ndarray) -> np.ndarray:
    counts = row_ptr[vs + 1] - row_ptr[vs]
    total = int(counts.sum())
    if total == 0:
        return np.array([], dtype=np.int64)
    out = np.empty(total, dtype=np.int64)
    offs = np.zeros(vs.size + 1, dtype=np.int64)
    np.cumsum(counts, out=offs[1:])
    # vectorized multi-range gather
    idx = np.repeat(row_ptr[vs] - offs[:-1], counts) + np.arange(total)
    out[:] = col[idx]
    return out


def _refine_once(g: Graph, part: np.ndarray, k: int, cap: int) -> np.ndarray:
    """Move boundary vertices to the neighbor-majority partition if balance
    permits.  One vectorized sweep (conflicts resolved by processing order)."""
    part = part.copy()
    # per-vertex edge counts toward each partition: sparse accumulate
    # find boundary vertices first
    src_p, dst_p = part[g.src], part[g.dst]
    boundary = np.unique(g.src[src_p != dst_p])
    if boundary.size == 0:
        return part
    if boundary.size > 20_000:  # cap the host-side sweep on huge graphs
        boundary = boundary[:: boundary.size // 20_000 + 1]
    sizes = np.bincount(part, minlength=k).astype(np.int64)
    row_ptr, col, _ = g.csr
    # process a sample of boundary vertices (cheap sweep)
    for v in boundary:
        nbrs = col[row_ptr[v] : row_ptr[v + 1]]
        if nbrs.size == 0:
            continue
        votes = np.bincount(part[nbrs], minlength=k)
        best = int(np.argmax(votes))
        cur = int(part[v])
        if best != cur and votes[best] > votes[cur] and sizes[best] < cap:
            part[v] = best
            sizes[best] += 1
            sizes[cur] -= 1
    return part
