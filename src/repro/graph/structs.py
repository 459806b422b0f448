"""Graph containers.

A ``Graph`` is a directed edge list over ``n_vertices`` (undirected graphs are
stored symmetrized).  A ``PartitionedGraph`` adds a vertex->partition map and
the subgraph (weakly-connected-component-within-partition) labeling that the
paper's metagraph is built from.

Construction is host-side numpy; the BSP/traversal layers consume the arrays
as jnp device arrays.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import cached_property

import numpy as np

INF_DIST = np.float32(np.inf)

#: Bound on per-layout block-map / block-range side caches.  Geometry keys are
#: (block_n, block_e) pairs; a run touches one or two geometries, so a small
#: LRU never thrashes while still bounding pathological sweeps.
_BLOCK_CACHE_MAX = 8


class BoundedCache(OrderedDict):
    """LRU-bounded side cache: at most ``max_entries`` live entries.

    The repo-wide cache discipline (checked by ``repro.analysis`` rule AL02):
    every long-lived dict cache must be bounded, and its keys must be
    *coerced* scalars/tuples (``int(...)``, ``str(...)``, canonical layout
    keys via ``mesh_layout_key``) so dtype or type aliases of the same value
    hit one entry instead of growing the cache.
    """

    def __init__(self, max_entries: int, *args):
        super().__init__(*args)
        self.max_entries = int(max_entries)

    def put(self, key, value):
        """Insert ``key`` as most-recently-used and evict past the bound."""
        self[key] = value
        self.move_to_end(key)
        while len(self) > self.max_entries:
            self.popitem(last=False)
        return value

    def get_or_build(self, key, build):
        """Return the cached value for ``key``, building (and bounding) on miss."""
        if key in self:
            self.move_to_end(key)
            return self[key]
        return self.put(key, build())


def block_ranges_for(
    dst: np.ndarray, n: int, block_n: int, block_e: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Per-row-block contiguous edge-block span for an ascending ``dst``
    array: (start [NB], count [NB], t_max).

    Because ``dst`` is sorted, the set of edge blocks intersecting a row
    block ``[ob*block_n, (ob+1)*block_n)`` is a contiguous range of edge
    blocks -- representable as a start index and a count, which is what the
    block-skipping relax kernel scalar-prefetches.  ``t_max = max(count)``
    bounds the kernel's inner grid dimension (vs ``ceil(E/block_e)`` for a
    dense grid that tests intersection per tile).  Shared by
    ``kernels.bfs_relax.make_relax_fn`` (dense engine) and
    ``MeshEdgeLayout.local_block_map``/``wire_block_map`` (per-device maps).
    """
    dst = np.asarray(dst)
    e = int(dst.shape[0])
    nb = max(1, -(-n // block_n))
    if e == 0:
        return np.zeros(nb, np.int32), np.zeros(nb, np.int32), 1
    neb = -(-e // block_e)
    firsts = dst[np.arange(neb) * block_e]
    lasts = dst[np.minimum(np.arange(1, neb + 1) * block_e, e) - 1]
    lo = firsts // block_n  # first row block each edge block touches
    hi = lasts // block_n  # last row block each edge block touches
    rows = np.arange(nb)
    start = np.searchsorted(hi, rows, side="left").astype(np.int32)
    end = np.searchsorted(lo, rows, side="right").astype(np.int32)
    count = np.maximum(end - start, 0).astype(np.int32)
    return start, count, max(1, int(count.max()))


def device_block_ranges(
    rows: np.ndarray, valid: np.ndarray, n: int, block_n: int, block_e: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """``block_ranges_for`` over one mesh device's valid edges.

    A device's padded tail (identity candidates aimed at its last row or
    slot) is no work for the kernel.  Counted in, it would hand that last
    row block every padded edge block, and ``t_max`` would grow with the
    largest device's edge count.  Valid edges are a prefix of each row.
    """
    return block_ranges_for(
        rows[: int(np.count_nonzero(valid))], n, block_n, block_e
    )


@dataclasses.dataclass(frozen=True)
class CsrEdgeLayout:
    """Static destination-sorted edge layout, built once per (sub)edge-set.

    The traversal engine and its relax kernel (``make_relax_fn``) consume
    edges in this fixed order for the lifetime of a graph, which (a) lets
    every segment reduction take the ``indices_are_sorted`` fast path, (b)
    needs no per-call ``argsort``, and (c) makes the per-tile destination
    ranges *static*, so the kernel grid can skip (row_block, edge_block)
    tiles that provably hold no in-range edge.

    Contract: ``dst`` is ascending; ``src``/``weights`` are permuted to match.
    ``perm`` retains the applied permutation (indices into the edge arrays the
    layout was built from) so per-program *edge-weight planes* -- alternative
    ``[E]`` value arrays such as PageRank's ``1/out_degree[src]`` -- can be
    permuted into layout order without re-sorting (``graph.program``).
    """

    n_vertices: int
    src: np.ndarray  # [E] int32, reordered by dst
    dst: np.ndarray  # [E] int32, ascending
    weights: np.ndarray  # [E] float32, reordered by dst
    perm: np.ndarray | None = None  # [E] int64 indices into the input order

    @property
    def n_edges(self) -> int:
        return int(self.dst.shape[0])


def mesh_layout_key(
    device_of_part: np.ndarray, n_devices: int, generation: int = 0
) -> tuple:
    """Canonical cache key of a mesh layout: ``n_devices`` plus the *coerced*
    partition -> device map's shape, dtype, and bytes, plus the graph's
    edge-delta ``generation``.

    Computed after the int32 coercion every consumer goes through, so callers
    passing the same placement with different dtypes (an int64 plan row vs an
    int32 stored map) hit one entry -- while ``tobytes()`` of the uncoerced
    array (the dtype/shape-blind key this replaces) would let two different
    maps alias one buffer and serve a stale layout under dynamic re-layout.

    ``generation`` is the streaming-mutation counter
    (``PartitionedGraph.__dict__['_delta_generation']``, bumped by
    ``graph.deltas``): two layouts of the same placement built before and
    after a delta merge carry different edge content under identical shapes,
    so the generation must be part of every key derived from this one --
    otherwise a mutate -> merge -> mutate cycle could serve a stale layout
    out of a shape-keyed cache (the JX04 delta-cycle audit pins this).
    """
    coerced = np.ascontiguousarray(device_of_part, dtype=np.int32)
    return (
        int(n_devices), coerced.shape, coerced.dtype.str, coerced.tobytes(),
        int(generation),
    )


@dataclasses.dataclass(frozen=True)
class MeshEdgeLayout:
    """Static mesh-aware extension of ``CsrEdgeLayout`` (one per device map).

    Extends the partitioned dst-sorted layout to a fixed assignment of
    partitions onto ``n_devices`` mesh devices so that every device's shard is
    a *fixed-shape* slice and every collective has a *static* payload:

      * vertices are permuted device-major and padded to ``n_pad`` rows per
        device (``pos_of_vertex``/``vertex_of_pos``); sharded traversal state
        is ``[S, n_devices * n_pad]`` split on the trailing axis,
      * local (within-partition) edges are grouped per owning device and
        padded to ``e_local_pad``, endpoints renumbered to device-local rows
        (both endpoints of a local edge share a device because a partition is
        never split across devices),
      * remote (cross-partition) edges are grouped by
        ``(src_device, dst_device)`` block; within each block the *distinct*
        destination vertices define static wire slots (``w_pad`` slots per
        block), so the superstep-boundary exchange aggregates per-destination
        minima **before** the collective -- one message per
        ``(dst_vertex, dst_device)``, not one per edge -- and the all-to-all
        payload is the fixed ``[n_devices, w_pad]`` buffer,
      * optionally (``mirror_degree`` is not None), *hub* destinations --
        vertices whose cross-partition in-degree meets the threshold -- are
        pulled out of the wire plane into a structurally identical *mirror*
        plane: every source device holds one mirror slot per
        ``(owner_device, hub)`` it sends into (``m_pad`` slots per block),
        remote edges targeting a hub are rewritten to target the local
        mirror, and a second all-to-all syncs each mirror to its owner once
        per superstep.  The mirror cache lets the engine suppress re-sends
        of unimproved hub values, which is where the wire savings come from
        (``mesh_exchange`` docstring has the exactness argument).

    All index arrays carry explicit validity masks; padded entries are wired
    to contribute identity values (``inf`` under min, ``0`` under sum), so no
    consumer needs data-dependent shapes.  Built host-side once per
    ``(PartitionedGraph, device_of_part)`` by
    ``partition.mesh_edge_layout``; the shard_map program in
    ``graph.mesh_exchange`` consumes it verbatim.

    ``l_eid``/``r_eid`` map every per-device edge slot back to its row in the
    partition layout's dst-sorted local/remote edge sets, so a per-program
    edge-weight plane (``graph.program.VertexProgram.edge_plane``) can be
    scattered into the padded per-device shape without rebuilding the layout.

    The layout is also the single owner of the *state indexing* helpers
    (``state_index_of_vertex`` / ``gather_global``) shared by the dense and
    mesh engines.
    """

    n_devices: int
    n_vertices: int
    n_parts: int
    device_of_part: np.ndarray  # [P] int32 owning device per partition
    # -- vertex shard views --------------------------------------------------
    n_pad: int  # padded vertex rows per device
    pos_of_vertex: np.ndarray  # [n] int64: device-major padded position
    vertex_of_pos: np.ndarray  # [D * n_pad] int64, -1 on padding rows
    part_of_pos: np.ndarray  # [D, n_pad] int32 (0 on padding; masked by valid)
    pos_valid: np.ndarray  # [D, n_pad] bool
    # -- per-device local edges (device-local dst ascending) -----------------
    e_local_pad: int
    lsrc: np.ndarray  # [D, e_local_pad] int32 device-local src row
    ldst: np.ndarray  # [D, e_local_pad] int32 device-local dst row, ascending
    lw: np.ndarray  # [D, e_local_pad] float32
    lvalid: np.ndarray  # [D, e_local_pad] bool
    l_eid: np.ndarray  # [D, e_local_pad] int64 row in the dst-sorted local set
    # -- per-device remote out-edges, (dst_device, dst_vertex)-sorted --------
    e_remote_pad: int
    w_pad: int  # wire slots per (src_device, dst_device) block
    rsrc: np.ndarray  # [D, e_remote_pad] int32 device-local src row
    rw: np.ndarray  # [D, e_remote_pad] float32
    rslot: np.ndarray  # [D, e_remote_pad] int32 in [0, D*w_pad), ascending
    rvalid: np.ndarray  # [D, e_remote_pad] bool
    r_eid: np.ndarray  # [D, e_remote_pad] int64 row in the dst-sorted remote set
    # -- receive side: wire slot -> device-local dst row ---------------------
    recv_idx: np.ndarray  # [D_recv, D_send, w_pad] int32 (0 on padding slots)
    # -- static exchange metadata (bench / diagnostics) ----------------------
    wire_slots: np.ndarray  # [D_send, D_recv] int64 distinct-dst slot counts
    remote_block_edges: np.ndarray  # [D_send, D_recv] int64 raw edge counts
    # -- hub mirroring (all fields zero-width when mirror_degree selects no
    # hubs; the defaults below are only placeholders -- ``_build_mesh_layout``
    # always constructs every field explicitly) ------------------------------
    mirror_degree: int | None = None  # threshold the layout was built with
    e_mirror_pad: int = 0  # padded hub edges per source device
    m_pad: int = 0  # mirror slots per (src_device, owner_device) block
    msrc: np.ndarray | None = None  # [D, e_mirror_pad] int32 device-local src
    mw: np.ndarray | None = None  # [D, e_mirror_pad] float32
    mslot: np.ndarray | None = None  # [D, e_mirror_pad] int32 in [0, D*m_pad)
    mvalid: np.ndarray | None = None  # [D, e_mirror_pad] bool
    m_eid: np.ndarray | None = None  # [D, e_mirror_pad] int64 remote-set row
    mrecv_idx: np.ndarray | None = None  # [D_recv, D_send, m_pad] int32
    mirror_slots: np.ndarray | None = None  # [D_send, D_recv] int64 hub slots
    mirror_block_edges: np.ndarray | None = None  # [D_send, D_recv] int64
    # -- streaming mutations -------------------------------------------------
    delta_generation: int = 0  # graph's edge-delta counter at build time

    @property
    def state_width(self) -> int:
        """Width of the sharded state axis: ``n_devices * n_pad``."""
        return self.n_devices * self.n_pad

    @property
    def layout_key(self) -> tuple:
        """This layout's canonical cache key (``mesh_layout_key`` of its own
        map and delta generation plus the mirror knob) -- what the mesh
        program's per-layout const/jit caches hash.  Including the generation
        keeps a post-merge layout from aliasing its pre-merge twin: the two
        share every shape and the placement bytes, but their edge content
        differs."""
        return mesh_layout_key(
            self.device_of_part, self.n_devices, self.delta_generation
        ) + (self.mirror_degree,)

    # -- shared state indexing (one implementation for dense + mesh) ---------

    @property
    def state_index_of_vertex(self) -> np.ndarray:
        """[n] position of each global vertex in the padded sharded state
        axis -- the one source of truth for addressing carried traversal
        state (the engine's dense path uses the identity instead)."""
        return self.pos_of_vertex

    def gather_global(self, state_rows: np.ndarray) -> np.ndarray:
        """Map padded device-major state ``[..., D * n_pad]`` back to global
        vertex order ``[..., n]``."""
        return np.asarray(state_rows)[..., self.pos_of_vertex]

    # -- per-device static block maps (Pallas relax-kernel backend) ----------
    #
    # Each device's reduction problem is exactly the block-skipping kernel's
    # shape: ``ldst[d]`` is ascending over ``n_pad`` device-local rows (pad
    # value ``n_pad - 1``) and ``rslot[d]`` is ascending over
    # ``n_devices * w_pad`` wire slots (pad value ``D * w_pad - 1``), so both
    # admit the contiguous edge-block span representation of
    # ``block_ranges_for``.  Padded edges point at *real* rows but carry
    # identity candidates, so they are reduction no-ops, and the maps span
    # only each device's valid prefix (``device_block_ranges``).  Maps are cached per
    # geometry in ``__dict__['_block_maps']`` (the frozen-dataclass side cache
    # shared with ``_build_info``); the incremental mesh rebuild in
    # ``partition._build_mesh_layout`` carries unchanged device rows forward.

    def _block_map(self, kind: str, block_n: int, block_e: int):
        key = (kind, int(block_n), int(block_e))
        cache = self.__dict__.get("_block_maps")
        if not isinstance(cache, BoundedCache):
            cache = BoundedCache(_BLOCK_CACHE_MAX, cache or ())
            self.__dict__["_block_maps"] = cache

        def build():
            if kind == "local":
                rows, valid, nseg = self.ldst, self.lvalid, self.n_pad
            elif kind == "mirror":
                rows, valid = self.mslot, self.mvalid
                nseg = self.n_devices * self.m_pad
            else:
                rows, valid = self.rslot, self.rvalid
                nseg = self.n_devices * self.w_pad
            per_dev = [
                device_block_ranges(rows[d], valid[d], nseg, block_n, block_e)
                for d in range(self.n_devices)
            ]
            start = np.stack([p[0] for p in per_dev])
            count = np.stack([p[1] for p in per_dev])
            return (start, count, max(1, int(count.max())))

        return cache.get_or_build(key, build)

    def local_block_map(self, block_n: int, block_e: int):
        """(start [D, NB], count [D, NB], t_max) over per-device local edges
        (``ldst`` rows, ``n_pad`` segments)."""
        return self._block_map("local", block_n, block_e)

    def wire_block_map(self, block_n: int, block_e: int):
        """(start [D, NBw], count [D, NBw], t_max) over per-device remote
        edges (``rslot`` rows, ``n_devices * w_pad`` wire-slot segments)."""
        return self._block_map("wire", block_n, block_e)

    def mirror_block_map(self, block_n: int, block_e: int):
        """(start [D, NBm], count [D, NBm], t_max) over per-device hub edges
        (``mslot`` rows, ``n_devices * m_pad`` mirror-slot segments)."""
        return self._block_map("mirror", block_n, block_e)


def dst_sorted_layout(
    n_vertices: int,
    src: np.ndarray,
    dst: np.ndarray,
    weights: np.ndarray | None = None,
) -> CsrEdgeLayout:
    """Build the static dst-sorted layout for an edge set (host-side, once)."""
    order = np.argsort(dst, kind="stable")
    w = (
        np.ones(src.shape[0], dtype=np.float32)
        if weights is None
        else weights.astype(np.float32)
    )
    return CsrEdgeLayout(
        n_vertices=n_vertices,
        src=src[order].astype(np.int32),
        dst=dst[order].astype(np.int32),
        weights=w[order],
        perm=order.astype(np.int64),
    )


@dataclasses.dataclass(frozen=True)
class Graph:
    """Directed graph as an edge list. ``weights`` default to 1.0 (BFS)."""

    n_vertices: int
    src: np.ndarray  # [E] int32
    dst: np.ndarray  # [E] int32
    weights: np.ndarray | None = None  # [E] float32 or None (unit weights)

    def __post_init__(self):
        assert self.src.dtype == np.int32 and self.dst.dtype == np.int32
        assert self.src.shape == self.dst.shape

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    @cached_property
    def edge_weights(self) -> np.ndarray:
        if self.weights is not None:
            return self.weights.astype(np.float32)
        return np.ones(self.n_edges, dtype=np.float32)

    @cached_property
    def csr(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(row_ptr [n+1], col_idx [E], edge_id [E]) sorted by src."""
        order = np.argsort(self.src, kind="stable")
        col = self.dst[order]
        counts = np.bincount(self.src, minlength=self.n_vertices)
        row_ptr = np.zeros(self.n_vertices + 1, dtype=np.int64)
        np.cumsum(counts, out=row_ptr[1:])
        return row_ptr, col, order.astype(np.int64)

    @cached_property
    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n_vertices).astype(np.int64)

    def symmetrized(self) -> "Graph":
        """Return graph with both edge directions present (deduplicated)."""
        s = np.concatenate([self.src, self.dst])
        d = np.concatenate([self.dst, self.src])
        w = None
        if self.weights is not None:
            w = np.concatenate([self.weights, self.weights])
        key = s.astype(np.int64) * self.n_vertices + d
        _, idx = np.unique(key, return_index=True)
        return Graph(
            self.n_vertices,
            s[idx].astype(np.int32),
            d[idx].astype(np.int32),
            None if w is None else w[idx].astype(np.float32),
        )


def _label_propagation_components(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Connected-component labels via vectorized min-label propagation.

    Treats edges as undirected.  Converges in O(component diameter) sweeps;
    each sweep is two ``np.minimum.at`` scatters, so large low-diameter graphs
    converge in a handful of passes.
    """
    labels = np.arange(n, dtype=np.int64)
    while True:
        prev = labels.copy()
        # propagate min label across edges both directions
        np.minimum.at(labels, dst, labels[src])
        np.minimum.at(labels, src, labels[dst])
        # pointer jumping: labels point at representative labels
        labels = labels[labels]
        if np.array_equal(labels, prev):
            break
    # compact to 0..k-1
    _, compact = np.unique(labels, return_inverse=True)
    return compact.astype(np.int64)


@dataclasses.dataclass(frozen=True)
class PartitionedGraph:
    """A Graph plus a vertex partition map and derived subgraph labeling.

    Terms follow the paper (s3.1):
      * ``part_of_vertex[v]``     -- partition id in [0, n_parts)
      * local edge                -- src and dst in same partition
      * remote edge               -- crosses partitions
      * subgraph                  -- WCC of the local-edge graph within one
                                     partition; ``subgraph_of_vertex[v]`` is a
                                     globally unique subgraph id
    """

    graph: Graph
    n_parts: int
    part_of_vertex: np.ndarray  # [n] int32

    def __post_init__(self):
        assert self.part_of_vertex.shape == (self.graph.n_vertices,)

    # -- edge classification ------------------------------------------------
    @cached_property
    def edge_src_part(self) -> np.ndarray:
        return self.part_of_vertex[self.graph.src]

    @cached_property
    def edge_dst_part(self) -> np.ndarray:
        return self.part_of_vertex[self.graph.dst]

    @cached_property
    def is_local_edge(self) -> np.ndarray:
        return self.edge_src_part == self.edge_dst_part

    @property
    def n_local_edges(self) -> int:
        return int(self.is_local_edge.sum())

    @property
    def n_remote_edges(self) -> int:
        return self.graph.n_edges - self.n_local_edges

    @property
    def edge_cut_fraction(self) -> float:
        return self.n_remote_edges / max(1, self.graph.n_edges)

    # -- subgraphs (WCCs within partitions) ---------------------------------
    @cached_property
    def subgraph_of_vertex(self) -> np.ndarray:
        """Globally-unique subgraph id per vertex.

        Computed as WCC over local edges only, then components that span a
        partition are (by construction) impossible, so each component lies in
        exactly one partition.
        """
        local = self.is_local_edge
        comp = _label_propagation_components(
            self.graph.n_vertices, self.graph.src[local], self.graph.dst[local]
        )
        # Vertices in different partitions must never share a subgraph id even
        # if they were isolated (comp would still separate them since no local
        # edge joins partitions) -- comp is already correct; just compact.
        return comp

    @property
    def n_subgraphs(self) -> int:
        return int(self.subgraph_of_vertex.max()) + 1

    @cached_property
    def part_of_subgraph(self) -> np.ndarray:
        """[n_subgraphs] partition owning each subgraph."""
        out = np.zeros(self.n_subgraphs, dtype=np.int32)
        out[self.subgraph_of_vertex] = self.part_of_vertex
        return out

    @cached_property
    def subgraph_sizes(self) -> tuple[np.ndarray, np.ndarray]:
        """(n_vertices [S], n_local_edges [S]) per subgraph."""
        nv = np.bincount(self.subgraph_of_vertex, minlength=self.n_subgraphs)
        sg_src = self.subgraph_of_vertex[self.graph.src]
        local = self.is_local_edge
        ne = np.bincount(sg_src[local], minlength=self.n_subgraphs)
        return nv.astype(np.int64), ne.astype(np.int64)

    @cached_property
    def partition_sizes(self) -> tuple[np.ndarray, np.ndarray]:
        """(n_vertices [P], n_local_edges [P]) per partition."""
        nv = np.bincount(self.part_of_vertex, minlength=self.n_parts)
        ne = np.bincount(self.edge_src_part[self.is_local_edge], minlength=self.n_parts)
        return nv.astype(np.int64), ne.astype(np.int64)

    def partition_bytes(self, bytes_per_vertex: int = 16, bytes_per_edge: int = 8) -> np.ndarray:
        """Approximate serialized size per partition, for data-movement cost."""
        nv, ne = self.partition_sizes
        return nv * bytes_per_vertex + ne * bytes_per_edge

    def balance_factor(self) -> float:
        """max partition vertex count / mean (paper uses METIS load factor 1.03)."""
        nv, _ = self.partition_sizes
        return float(nv.max() / max(1.0, nv.mean()))
