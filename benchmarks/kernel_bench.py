"""Pallas kernel bench: correctness digest + TPU v5e roofline projections
per kernel at production shapes.

The digest runs each kernel against its reference: compiled on an
accelerator, through the Pallas interpreter only when the platform is the
CPU.  The projection rows time nothing.  They report the terms a TPU v5e run
would be bounded by: FLOPs, HBM bytes, arithmetic intensity, and the
projected roofline time max(flops/peak, bytes/bw) per call from the chip's
published peaks, plus the VMEM working set implied by the chosen BlockSpecs
(must stay under ~16 MiB).  Each row says so in its ``projection`` field.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

#: published TPU v5e peaks (Google Cloud documentation, "TPU v5e"); the
#: rows below are projections from them, not measurements
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s
PROJECTION = "TPU v5e published peaks (projection, not a measurement)"


def _interpret() -> bool:
    """Pallas interpreter only on the CPU; compiled kernels elsewhere."""
    return jax.default_backend() == "cpu"


def _roofline_row(name, flops, bytes_, vmem_bytes, correct):
    t_c = flops / PEAK_FLOPS
    t_m = bytes_ / HBM_BW
    bound = "compute" if t_c > t_m else "memory"
    return dict(
        name=name,
        gflops=flops / 1e9,
        gbytes=bytes_ / 1e9,
        intensity=flops / max(bytes_, 1),
        roofline_us=max(t_c, t_m) * 1e6,
        bound=bound,
        vmem_mib=vmem_bytes / 2**20,
        correct=correct,
        projection=PROJECTION,
    )


def bench_flash() -> dict:
    from repro.kernels.flash_attention import flash_attention, reference_attention

    # correctness probe at reduced shape
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, interpret=_interpret())
    err = float(jnp.max(jnp.abs(out - reference_attention(q, k, v))))

    # production shape: mixtral prefill_32k per-device slice
    # b=2 (32/16), h=3 (48/16), s=32768, d=128, window 4096
    b, h, s, d, win = 2, 3, 32768, 128, 4096
    n_pairs = b * h * s * win  # causal+window ~ s*win scores
    flops = 4 * n_pairs * d  # qk + pv
    bytes_ = (2 * b * s * h * d + 2 * b * s * 1 * d) * 2  # q,o + k,v (shared kv head)
    vmem = (128 * d + 2 * 128 * d + 128 * 128 + 3 * 128 * 128) * 4
    return _roofline_row("flash_attention(mixtral prefill32k/dev)", flops, bytes_, vmem, err < 1e-4)


def bench_segment_sum() -> dict:
    from repro.kernels.segment_sum import reference_segment_sum, sorted_segment_sum

    rng = np.random.default_rng(0)
    ids = jnp.asarray(np.sort(rng.integers(0, 256, 2048)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(2048, 64)), jnp.float32)
    out = sorted_segment_sum(
        ids, vals, 256, assume_sorted=True, interpret=_interpret()
    )
    err = float(jnp.max(jnp.abs(out - reference_segment_sum(ids, vals, 256))))

    # production: ogb_products per-device slice E=242k edges (62M/256), D=128
    e, d, n = 242_000, 128, 9_600
    # band kernel: each edge contributes one one-hot MXU row: 2*bE*bN*D per
    # on-band block; with sorted ids ~1 on-band block per edge block
    be, bn = 512, 256
    n_blocks = e // be
    flops = n_blocks * 2 * be * bn * d
    bytes_ = (e * d + n * d) * 4 + e * 4
    vmem = (be * d + bn * d + be) * 4
    return _roofline_row("segment_sum(ogb_products/dev)", flops, bytes_, vmem, err < 1e-4)


def bench_bfs_relax() -> dict:
    from repro.analysis.jaxpr_audit import check_hot_path, check_pallas_grids
    from repro.graph.program import SsspProgram
    from repro.graph.structs import dst_sorted_layout
    from repro.kernels.bfs_relax import make_relax_fn, reference_bfs_relax

    rng = np.random.default_rng(1)
    n, e = 1024, 4096
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    w = np.ones((e,), np.float32)
    layout = dst_sorted_layout(n, src, dst, w)
    dist = jnp.full((n,), jnp.inf).at[0].set(0.0)
    frontier = jnp.zeros((n,), bool).at[0].set(True)

    # the dense engine's kernel entry: the block map is built and uploaded
    # once, outside the trace, and passed in as an argument
    prog = SsspProgram()
    relax, blockmap = make_relax_fn(
        layout.dst, n, reduce=prog.reduce, interpret=_interpret()
    )

    @jax.jit
    def relax_pass(blockmap, src, w, dist, frontier):
        # candidates as the engine builds them: the frontier-masked relax of
        # each arc's gathered source state
        cand = jnp.where(
            frontier[:, src], prog.relax(dist[:, src], w), prog.identity
        )
        return relax(blockmap, cand, dist)

    args = (
        blockmap, jnp.asarray(layout.src), jnp.asarray(layout.weights),
        dist[None], frontier[None],
    )
    # the digest also audits the exact jitted pass it checks: a degenerate
    # grid or a host callback here fails the bench, not just CI lint
    closed = jax.make_jaxpr(relax_pass)(*args)
    findings = check_hot_path(closed, "bench/bfs_relax")
    findings += check_pallas_grids(closed, "bench/bfs_relax", expect_kernel=True)
    assert not findings, "\n".join(str(f) for f in findings)

    out = relax_pass(*args)[0]
    ref = reference_bfs_relax(
        dist, frontier, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w)
    )
    err = float(
        jnp.nanmax(
            jnp.where(
                jnp.isfinite(out) | jnp.isfinite(ref),
                jnp.abs(jnp.nan_to_num(out, posinf=0) - jnp.nan_to_num(ref, posinf=0)),
                0.0,
            )
        )
    )

    # production: USRN-scale partition slice, E=7.3M edges, N=3M vertices.
    # The static block map enumerates only on-band tiles: with dst sorted,
    # each edge block spans ~1 row block, so tiles ~ E/bE (+ row-block inits)
    # instead of the dense (N/bN)*(E/bE) grid -- report the skip ratio.
    e, n = 7_300_000, 3_000_000
    be, bn = 512, 512
    dense_tiles = (n // bn) * (e // be)
    mapped_tiles = (e // be) + (n // bn)
    flops = mapped_tiles * be * bn  # compare+select per mapped tile
    bytes_ = (2 * e + 2 * n) * 4
    vmem = (2 * be + 2 * bn) * 4
    row = _roofline_row("bfs_relax(USRN partition)", flops, bytes_, vmem, err == 0.0)
    row["tile_skip_ratio"] = dense_tiles / mapped_tiles
    return row


def run(verbose: bool = True) -> list[dict]:
    rows = [bench_flash(), bench_segment_sum(), bench_bfs_relax()]
    if verbose:
        print(f"device: {jax.devices()[0].platform} {jax.devices()[0].device_kind}")
        print(f"roofline rows: {PROJECTION}")
        print("name,gflops,gbytes,intensity,roofline_us,bound,vmem_mib,correct")
        for r in rows:
            print(
                f"{r['name']},{r['gflops']:.2f},{r['gbytes']:.3f},"
                f"{r['intensity']:.1f},{r['roofline_us']:.1f},{r['bound']},"
                f"{r['vmem_mib']:.2f},{r['correct']}"
            )
            if "tile_skip_ratio" in r:
                print(f"  block map skips {r['tile_skip_ratio']:.0f}x dense-grid tiles")
        assert all(r["correct"] for r in rows), "kernel correctness failed"
    return rows


if __name__ == "__main__":
    run()
