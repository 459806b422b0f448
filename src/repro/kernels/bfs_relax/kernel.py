"""Program-generic frontier relaxation Pallas TPU kernel -- the paper's
per-superstep hot spot (GoFFish compute() = repeated edge relaxations).

Same TPU adaptation as segment_sum: candidate messages (program.relax of the
gathered source state, masked by the frontier -- the gather runs outside the
kernel where XLA schedules it) arrive sorted by destination; each
(row-block x edge-block) cell selects matching candidates into a dense
[bE, bN] matrix and reduces it columnwise.  The output tile initializes from
a caller-supplied base state, so one pass computes
``combine(base, segment_reduce(cand, dst))`` for the whole VertexProgram
algebra:

  * ``reduce="min"`` -- monotone programs (BFS / SSSP / WCC).  The tile op
    is a masked columnwise min against an identity fill (+inf for floats,
    iinfo.max for WCC's int32 labels); combine(base, .) is a second min, so
    the base doubles as the running output accumulator.
  * ``reduce="sum"`` -- stationary programs (PageRank).  The tile op reuses
    the ``sorted_segment_sum`` accumulate idiom (+= of the masked block)
    with a zero identity; the base (normally all-zero) seeds the
    accumulator, which lets callers chain local- and remote-plane passes.

``relax_kernel_blockmap`` is the one kernel.  A precomputed block map
(``block_ranges_for``: per row block, the contiguous span of edge blocks
that can hit it) is scalar-prefetched, so the grid enumerates only tiles
that provably contain in-range edges.  Sources ride the sublane axis in
tiles of up to ``SOURCE_TILE`` rows: one grid step fetches the dst tile and
builds its hit mask once, then reduces every source of the tile against it.
A tile of 8 rows (or all rows, when there are fewer) keeps every block's
last two dims legal for Mosaic; sources past a multiple of 8 are padded with
identity rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = float("inf")  # python scalar: jnp constants would be captured tracers


def _kernel_blockmap(
    start_ref,  # [NB] int32 scalar-prefetch: first edge block per row block
    cnt_ref,  # [NB] int32 scalar-prefetch: edge blocks per row block
    dst_ref,  # (1, bE) int32 sorted, padded with n_pad
    cand_ref,  # (sT, bE) candidates of the tile's sources (identity inactive)
    base_ref,  # (sT, bN) base state for (source tile, row block)
    o_ref,  # (sT, bN), persists across the t dimension
    *,
    block_n: int,
    block_e: int,
    s_tile: int,
    reduce: str,
    identity,
):
    oi = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = base_ref[...]

    # the block map guarantees blocks [start, start+cnt) intersect this row
    # block; tiles beyond cnt are clamped duplicates -- skip their compute
    @pl.when(t < cnt_ref[oi])
    def _relax():
        dst = dst_ref[0, :]
        rows = oi * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (block_e, block_n), 1
        )
        hit = dst[:, None] == rows  # shared by every source of the tile
        for r in range(s_tile):
            m = jnp.where(hit, cand_ref[r, :][:, None], identity)
            if reduce == "min":
                o_ref[r, :] = jnp.minimum(o_ref[r, :], m.min(axis=0))
            else:  # "sum": segment_sum accumulate idiom (identity == 0)
                o_ref[r, :] = o_ref[r, :] + m.sum(axis=0)


#: most sources one grid step reduces: one f32 sublane tile
SOURCE_TILE = 8


def relax_kernel_blockmap(
    start: jax.Array,  # [NB] int32 block map (see structs.block_ranges_for)
    cnt: jax.Array,  # [NB] int32
    dst_sorted: jax.Array,  # [Ep] int32 ascending, padded with n_pad
    cand: jax.Array,  # [S, Ep] candidates aligned with dst_sorted
    base: jax.Array,  # [S, Np] base state, combined into the output
    *,
    block_n: int,
    block_e: int,
    t_max: int,
    reduce: str = "min",
    interpret: bool = False,
) -> jax.Array:
    """Batched block-skipping ``combine(base, segment_reduce(cand, dst))``.

    ``reduce`` is "min" (monotone programs; identity follows the candidate
    dtype: +inf for floats, iinfo.max for ints) or "sum" (stationary
    programs; identity 0).  Padded dst entries must point past the last real
    row; padded candidates must carry the identity.  Output dtype follows
    ``base``.  The grid is ``(ceil(S / s_tile), NB, t_max)``.
    """
    s, e_pad = cand.shape
    n_pad = base.shape[1]
    assert e_pad % block_e == 0 and n_pad % block_n == 0
    assert reduce in ("min", "sum")
    n_eb = e_pad // block_e
    dt = jnp.dtype(base.dtype)
    if reduce == "sum":
        identity = dt.type(0)
    elif jnp.issubdtype(dt, jnp.floating):
        identity = dt.type(INF)
    else:
        identity = dt.type(jnp.iinfo(dt).max)
    s_tile = min(s, SOURCE_TILE)
    s_pad = -(-s // s_tile) * s_tile
    if s_pad != s:
        cand = jnp.pad(cand, ((0, s_pad - s), (0, 0)), constant_values=identity)
        base = jnp.pad(base, ((0, s_pad - s), (0, 0)), constant_values=identity)

    def _edge_block(s_i, oi, t, start, cnt):
        del s_i, cnt
        return (0, jnp.minimum(start[oi] + t, n_eb - 1))

    def _cand_block(s_i, oi, t, start, cnt):
        del cnt
        return (s_i, jnp.minimum(start[oi] + t, n_eb - 1))

    def _row_block(s_i, oi, t, start, cnt):
        del t, start, cnt
        return (s_i, oi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_pad // s_tile, n_pad // block_n, t_max),
        in_specs=[
            pl.BlockSpec((1, block_e), _edge_block),
            pl.BlockSpec((s_tile, block_e), _cand_block),
            pl.BlockSpec((s_tile, block_n), _row_block),
        ],
        out_specs=pl.BlockSpec((s_tile, block_n), _row_block),
    )
    kern = functools.partial(
        _kernel_blockmap,
        block_n=block_n,
        block_e=block_e,
        s_tile=s_tile,
        reduce=reduce,
        identity=identity,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, n_pad), dt),
        interpret=interpret,
    )(start, cnt, dst_sorted.reshape(1, e_pad), cand, base)
    return out[:s]

