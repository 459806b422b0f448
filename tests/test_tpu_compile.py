"""The relax kernel compiles for a TPU v5e at the widths of Graph500 scale 20.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described and not attached.  The widths are those of
``chip_smoke.py``'s graph (R-MAT scale 20, edge factor 16, 8 BFS-grown
parts): 2^20 rows, 22,629,626 local arcs (t_max 362 at block 512) and
9,576,222 remote arcs (t_max 467).  Each case must lower the kernel to a
Mosaic ``tpu_custom_call``: interpret mode cannot see a block shape the
chip refuses.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the suite's workers
all import this file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bfs_relax.kernel import relax_kernel_blockmap

BLOCK = 512
N_PAD = 1 << 20
E_LOCAL_PAD = 44_199 * BLOCK  # 22,629,626 local arcs, padded to the block
E_REMOTE_PAD = 18_704 * BLOCK  # 9,576,222 remote arcs
T_MAX_LOCAL = 362
T_MAX_REMOTE = 467


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "s,dtype,reduce,e_pad,t_max",
    [
        (1, jnp.float32, "min", E_LOCAL_PAD, T_MAX_LOCAL),
        (8, jnp.float32, "min", E_LOCAL_PAD, T_MAX_LOCAL),
        (16, jnp.float32, "min", E_LOCAL_PAD, T_MAX_LOCAL),
        (3, jnp.float32, "min", E_LOCAL_PAD, T_MAX_LOCAL),
        (8, jnp.int32, "min", E_LOCAL_PAD, T_MAX_LOCAL),
        (8, jnp.float32, "sum", E_LOCAL_PAD, T_MAX_LOCAL),
        (8, jnp.float32, "min", E_REMOTE_PAD, T_MAX_REMOTE),
    ],
    ids=[
        "s1-min-f32", "s8-min-f32", "s16-min-f32", "s3-min-f32",
        "s8-min-i32", "s8-sum-f32", "s8-min-f32-remote",
    ],
)
def test_relax_kernel_compiles_for_v5e(one_chip, s, dtype, reduce, e_pad, t_max):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def relax(start, cnt, dst, cand, base):
        return relax_kernel_blockmap(
            start, cnt, dst, cand, base,
            block_n=BLOCK, block_e=BLOCK, t_max=t_max, reduce=reduce,
        )

    nb = N_PAD // BLOCK
    compiled = jax.jit(relax).lower(
        sds((nb,), jnp.int32),
        sds((nb,), jnp.int32),
        sds((e_pad,), jnp.int32),
        sds((s, e_pad), dtype),
        sds((s, N_PAD), dtype),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
