"""jit'd wrappers around the relaxation kernels + the engine backend switch.

``bfs_relax`` is the general entry: computes candidates (XLA gather), sorts
by destination unless ``presorted=True``, pads to block multiples, runs the
dense-grid kernel.

``bfs_relax_csr`` is the static-layout fast path: edges come from a
``CsrEdgeLayout`` (dst already ascending -- no argsort, ever), the layout's
precomputed block map drives the block-skipping kernel, and a leading source
dimension batches multiple BFS sweeps through one kernel launch.

``relax_csr`` generalizes the same path over the whole ``VertexProgram``
algebra: ``reduce="min"`` (BFS/SSSP/WCC, identity-padded, dtype follows the
state -- WCC's int32 labels included) and ``reduce="sum"`` (PageRank,
reusing the segment-sum accumulate idiom).  The lower-level pieces both
engines build on:

  * ``relax_blockmap_call`` -- fully traced ``combine(base,
    segment_reduce(cand, dst))`` given a precomputed block map; safe inside
    ``jit``/``while_loop``/``shard_map`` (the mesh engine calls it per
    device shard).
  * ``make_relax_fn`` -- host-side builder for the dense engine: computes
    the static block map once, uploads it, and returns it with the traced
    relax fn that takes it as an argument.

Both engines select this path via ``backend`` in ``RELAX_BACKENDS``:
``"xla"`` (default; segment ops, right on CPU), ``"pallas"`` (compiled
kernels, TPU), ``"pallas-interpret"`` (kernel semantics on CPU -- the CI
parity mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structs import BoundedCache, block_ranges_for
from repro.kernels.bfs_relax.kernel import (
    SOURCE_TILE,
    bfs_relax_kernel,
    bfs_relax_kernel_blockmap,
    relax_kernel_blockmap,
)

RELAX_BACKENDS = ("xla", "pallas", "pallas-interpret")


def validate_backend(backend: str) -> bool:
    """Check an engine ``backend`` name; returns ``interpret`` for the kernel
    path (only meaningful when the backend is not ``"xla"``)."""
    if backend not in RELAX_BACKENDS:
        raise ValueError(f"backend must be one of {RELAX_BACKENDS}, got {backend!r}")
    return backend == "pallas-interpret"


def _block_dims(n: int, e: int, block_n: int, block_e: int) -> tuple[int, int, int, int]:
    """Clamp block sizes to the problem and round shapes up to multiples:
    (block_n, block_e, n_pad, e_pad).  Padded dst entries use the sentinel
    ``n_pad`` (>= every row block), padded candidates carry the reduction
    identity.  Degenerate inputs (``e < 8``, ``n < 8``, including ``e == 0``)
    still clamp blocks to >= 8, so the pads round up to *at least one full
    block* -- otherwise ``block_e > e_pad`` would collapse a grid dimension
    to zero and the output tile would never initialize."""
    block_e = min(block_e, max(8, e))
    block_n = min(block_n, max(8, n))
    e_pad = max(block_e, (e + block_e - 1) // block_e * block_e)
    n_pad = max(block_n, (n + block_n - 1) // block_n * block_n)
    return block_n, block_e, n_pad, e_pad


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_e", "interpret", "presorted")
)
def bfs_relax(
    dist: jax.Array,  # [N] f32
    frontier: jax.Array,  # [N] bool
    src: jax.Array,  # [E] int32
    dst: jax.Array,  # [E] int32
    w: jax.Array,  # [E] f32
    *,
    block_n: int = 512,
    block_e: int = 512,
    interpret: bool = False,
    presorted: bool = False,  # dst already ascending (static edge order)
) -> jax.Array:
    (n,) = dist.shape
    (e,) = src.shape
    cand = jnp.where(frontier[src], dist[src] + w, jnp.inf)
    if not presorted:
        order = jnp.argsort(dst)
        dst, cand = dst[order], cand[order]
    block_n, block_e, n_pad, e_pad = _block_dims(n, e, block_n, block_e)
    dst = jnp.pad(dst, (0, e_pad - e), constant_values=n_pad)
    cand = jnp.pad(cand, (0, e_pad - e), constant_values=jnp.inf)
    dist_p = jnp.pad(dist, (0, n_pad - n), constant_values=jnp.inf)
    out = bfs_relax_kernel(
        dst, cand, dist_p, block_n=block_n, block_e=block_e, interpret=interpret
    )
    return out[:n]


@functools.partial(
    jax.jit,
    static_argnames=("n", "block_n", "block_e", "t_max", "interpret"),
)
def _bfs_relax_csr_jit(
    dist,  # [S, N] f32
    frontier,  # [S, N] bool
    src,  # [E] int32 (dst-sorted order)
    dst,  # [E] int32 ascending
    w,  # [E] f32
    start,  # [NB] int32 block map
    cnt,  # [NB] int32
    *,
    n: int,
    block_n: int,
    block_e: int,
    t_max: int,
    interpret: bool,
):
    e = src.shape[0]
    cand = jnp.where(frontier[:, src], dist[:, src] + w, jnp.inf)
    _, _, n_pad, e_pad = _block_dims(n, e, block_n, block_e)
    dst_p = jnp.pad(dst, (0, e_pad - e), constant_values=n_pad)
    cand_p = jnp.pad(cand, ((0, 0), (0, e_pad - e)), constant_values=jnp.inf)
    dist_p = jnp.pad(dist, ((0, 0), (0, n_pad - n)), constant_values=jnp.inf)
    out = bfs_relax_kernel_blockmap(
        start,
        cnt,
        dst_p,
        cand_p,
        dist_p,
        block_n=block_n,
        block_e=block_e,
        t_max=t_max,
        interpret=interpret,
    )
    return out[:, :n]


#: bounded device-upload cache per layout.  PR 5's ``mesh_layout_key``
#: taught the layer that layout caches need canonical keys and a bound; the
#: entries here are keyed the same way -- by the *coerced* static inputs
#: (kind tag + int block geometry), never by array identity -- and LRU-bound
#: so sweeping block geometries (benchmarks do) cannot grow the cache
#: unboundedly per layout.
_DEVICE_CACHE_MAX = 8


def _device_cached(layout, key: tuple, build):
    """Fetch-or-build an entry in the layout's bounded device cache."""
    cache = layout.__dict__.get("_device_cache")
    if not isinstance(cache, BoundedCache):
        cache = BoundedCache(_DEVICE_CACHE_MAX)
        layout.__dict__["_device_cache"] = cache
    return cache.get_or_build(key, build)


def _layout_edges_on_device(layout):
    return _device_cached(
        layout,
        ("edges",),
        lambda: tuple(
            jnp.asarray(a) for a in (layout.src, layout.dst, layout.weights)
        ),
    )


def _layout_blockmap_on_device(layout, block_n: int, block_e: int):
    def build():
        start, cnt, t_max = layout.block_ranges(block_n, block_e)
        return jnp.asarray(start), jnp.asarray(cnt), t_max

    return _device_cached(
        layout, ("blockmap", int(block_n), int(block_e)), build
    )


def bfs_relax_csr(
    dist: jax.Array,  # [N] or [S, N] f32
    frontier: jax.Array,  # matching bool
    layout,  # CsrEdgeLayout (static, host-side)
    *,
    block_n: int = 512,
    block_e: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``min(dist, segment_min(cand, dst))`` over a static dst-sorted layout.

    Always takes the presorted path (the layout *is* the sort), and skips
    empty (row_block, edge_block) tiles via the layout's block map.  Accepts
    a batched ``[S, N]`` state to amortize kernel launches across sources.
    """
    squeeze = dist.ndim == 1
    if squeeze:
        dist, frontier = dist[None], frontier[None]
    n = dist.shape[1]
    e = layout.n_edges
    if e == 0:
        return dist[0] if squeeze else dist
    block_n, block_e, _, _ = _block_dims(n, e, block_n, block_e)
    src_d, dst_d, w_d = _layout_edges_on_device(layout)
    start_d, cnt_d, t_max = _layout_blockmap_on_device(layout, block_n, block_e)
    out = _bfs_relax_csr_jit(
        dist,
        frontier,
        src_d,
        dst_d,
        w_d,
        start_d,
        cnt_d,
        n=n,
        block_n=block_n,
        block_e=block_e,
        t_max=t_max,
        interpret=interpret,
    )
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# program-generic entry points (the engine backend)
# ---------------------------------------------------------------------------


def _identity_scalar(reduce: str, dtype):
    """The reduction identity matching the kernel's padding contract."""
    dt = np.dtype(dtype)
    if reduce == "sum":
        return dt.type(0)
    if np.issubdtype(dt, np.floating):
        return dt.type(np.inf)
    return dt.type(np.iinfo(dt).max)


def relax_blockmap_call(
    start: jax.Array,  # [NB] int32 block map rows (may be traced)
    cnt: jax.Array,  # [NB] int32
    dst: jax.Array,  # [E] int32 ascending (may be traced)
    cand: jax.Array,  # [S, E] candidates (identity where inactive)
    base: jax.Array,  # [S, N] base state
    *,
    reduce: str,
    block_n: int,
    block_e: int,
    t_max: int,
    interpret: bool = False,
) -> jax.Array:
    """Traced ``combine(base, segment_reduce(cand, dst))`` via the blockmap
    kernel: pads all operands to the block geometry and slices the result.

    Block geometry and ``t_max`` are static; everything else may be a
    tracer, so this is the form both engines call inside ``jit`` /
    ``lax.while_loop`` / ``shard_map``.  The caller's block map must have
    been built with the *clamped* geometry -- re-deriving the clamp here is
    idempotent with the caller's ``_block_dims`` call.
    """
    s, e = cand.shape
    n = base.shape[1]
    ident = _identity_scalar(reduce, base.dtype)
    bn, be, n_pad, e_pad = _block_dims(n, e, block_n, block_e)
    dst_p = jnp.pad(dst, (0, e_pad - e), constant_values=n_pad)
    cand_p = jnp.pad(cand, ((0, 0), (0, e_pad - e)), constant_values=ident)
    base_p = jnp.pad(base, ((0, 0), (0, n_pad - n)), constant_values=ident)
    out = relax_kernel_blockmap(
        start,
        cnt,
        dst_p,
        cand_p,
        base_p,
        block_n=bn,
        block_e=be,
        t_max=t_max,
        reduce=reduce,
        interpret=interpret,
    )
    return out[:, :n]


def relax_grid(
    dst: np.ndarray,  # [E] int32 ascending (static, host-side)
    n: int,
    s_batch: int,
    *,
    block_n: int = 512,
    block_e: int = 512,
) -> dict:
    """The grid of one blockmap-kernel pass over ``dst`` for ``s_batch``
    sources, as ``make_relax_fn`` would launch it: the clamped block
    geometry, row blocks, ``t_max``, the tiles that hold edges, and
    ``grid_steps`` (source tiles x row blocks x ``t_max``; 0 when there are
    no edges and no kernel runs)."""
    dst = np.asarray(dst)
    e = int(dst.shape[0])
    if e == 0:
        return {"block_n": 0, "block_e": 0, "row_blocks": 0, "t_max": 0,
                "tiles_with_edges": 0, "grid_steps": 0}
    bn, be, n_pad, _ = _block_dims(n, e, block_n, block_e)
    _, cnt, t_max = block_ranges_for(dst, n, bn, be)
    row_blocks = n_pad // bn
    source_tiles = -(-s_batch // min(s_batch, SOURCE_TILE))
    return {
        "block_n": bn,
        "block_e": be,
        "row_blocks": row_blocks,
        "t_max": int(t_max),
        "tiles_with_edges": int(cnt.sum()),
        "grid_steps": source_tiles * row_blocks * int(t_max),
    }


def make_relax_fn(
    dst: np.ndarray,  # [E] int32 ascending (static, host-side)
    n: int,
    *,
    reduce: str,
    block_n: int = 512,
    block_e: int = 512,
    interpret: bool = False,
):
    """Host-side builder for the dense engine: compute the static block map
    for a dst-sorted edge array once and upload it.

    Returns ``(relax, blockmap)``: ``relax(blockmap, cand [S, E], base [S,
    n]) -> [S, n]`` runs the block-skipping kernel, and ``blockmap`` holds
    the uploaded ``(start, cnt, dst)`` arrays.  A jitted caller passes
    ``blockmap`` in as an argument; closing over it would embed the arrays
    in the compiled program as constants.  With ``e == 0``, ``relax`` is the
    combine identity (returns ``base``) and ``blockmap`` is empty."""
    dst = np.asarray(dst)
    e = int(dst.shape[0])
    if e == 0:
        return (lambda blockmap, cand, base: base), ()
    bn, be, _, _ = _block_dims(n, e, block_n, block_e)
    start, cnt, t_max = block_ranges_for(dst, n, bn, be)
    blockmap = (jnp.asarray(start), jnp.asarray(cnt), jnp.asarray(dst))

    def relax(blockmap, cand, base):
        start_d, cnt_d, dst_d = blockmap
        return relax_blockmap_call(
            start_d,
            cnt_d,
            dst_d,
            cand,
            base,
            reduce=reduce,
            block_n=bn,
            block_e=be,
            t_max=t_max,
            interpret=interpret,
        )

    return relax, blockmap


def relax_csr(
    program,  # graph.program.VertexProgram
    state: jax.Array,  # [N] or [S, N], dtype = program.dtype
    frontier: jax.Array,  # matching bool
    layout,  # CsrEdgeLayout (static, host-side)
    *,
    block_n: int = 512,
    block_e: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """One program-generic relaxation pass over a static dst-sorted layout.

    Computes ``cand = where(frontier[src], program.relax(state[src], w),
    identity)`` (XLA gather) then reduces per destination with the
    block-skipping kernel.  Matches the engine's consumption of each
    reduction: monotone programs (``reduce="min"``) return
    ``combine(state, segment_min(cand, dst))``; stationary programs
    (``reduce="sum"``) return the pre-apply accumulator
    ``segment_sum(cand, dst)``.

    The plane value fed to ``program.relax`` is ``layout.weights`` -- for
    programs with a non-graph ``plane_key`` (BFS unit hops, PageRank
    ``1/out_degree``) build the layout with that plane as its weights
    (``resolve_edge_plane`` + the layout's retained ``perm``).
    """
    squeeze = state.ndim == 1
    if squeeze:
        state, frontier = state[None], frontier[None]
    n = state.shape[1]
    e = layout.n_edges
    ident = _identity_scalar(program.reduce, state.dtype)
    if e == 0:
        out = (
            state
            if program.reduce == "min"
            else jnp.full_like(state, ident)
        )
        return out[0] if squeeze else out
    bn, be, _, _ = _block_dims(n, e, block_n, block_e)
    src_d, dst_d, w_d = _layout_edges_on_device(layout)
    start_d, cnt_d, t_max = _layout_blockmap_on_device(layout, bn, be)
    cand = jnp.where(
        frontier[:, src_d], program.relax(state[:, src_d], w_d), ident
    )
    base = (
        state
        if program.reduce == "min"
        else jnp.full_like(state, ident)
    )
    out = relax_blockmap_call(
        start_d,
        cnt_d,
        dst_d,
        cand,
        base,
        reduce=program.reduce,
        block_n=bn,
        block_e=be,
        t_max=t_max,
        interpret=interpret,
    )
    return out[0] if squeeze else out
