"""The engines' two entries to the relax kernel, and the backend switch.

Both reach the one kernel, ``relax_kernel_blockmap``, which computes
``combine(base, segment_reduce(cand, dst))`` over a dst-sorted edge array
for the whole ``VertexProgram`` algebra: ``reduce="min"`` (BFS / SSSP /
WCC; identity-padded, dtype follows the state, WCC's int32 labels included)
and ``reduce="sum"`` (PageRank).  The caller gathers the candidates,
``where(frontier[:, src], program.relax(state[:, src], w), identity)``.

  * ``make_relax_fn`` -- the dense engine's entry (``graph.traversal``): a
    host-side builder that computes the static block map of a dst-sorted
    edge array once, uploads it, and returns it with the traced relax fn
    that takes it as an argument.
  * ``relax_blockmap_call`` -- the mesh engine's entry
    (``graph.mesh_exchange``, once per device shard): fully traced given a
    precomputed block map, so it is safe inside
    ``jit``/``while_loop``/``shard_map``.  ``make_relax_fn``'s fn calls it.

``relax_grid`` reports the grid one ``make_relax_fn`` pass launches.  Both
engines select the kernel via ``backend`` in ``RELAX_BACKENDS``: ``"xla"``
(default; segment ops, right on CPU), ``"pallas"`` (the compiled kernel,
TPU), ``"pallas-interpret"`` (kernel semantics on CPU -- the CI parity
mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structs import block_ranges_for
from repro.kernels.bfs_relax.kernel import SOURCE_TILE, relax_kernel_blockmap

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

