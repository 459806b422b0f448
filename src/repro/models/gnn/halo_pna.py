"""PNA over halo-exchange sharding (shard_map): the paper-bridge optimization.

Mathematically identical to ``pna_forward`` (the message MLP is row-wise, so
applying it to [own | halo] rows then gathering equals gathering then
applying), but executed with one boundary all-to-all per layer instead of
full-table all-gathers/all-reduces: wire bytes ~ P * Smax * F (the planned
edge cut) instead of N * F per collective.  Plans come from
``repro.dist.halo.build_halo_plan`` -- i.e. from the same BFS-grow
partitioner the paper's elastic placement layer uses.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.dist.halo import halo_gather
from repro.models.gnn.message_passing import layer_norm, mlp_apply, segment_reduce
from repro.models.gnn.pna import init_pna  # same parameters as dense PNA

__all__ = ["init_pna", "pna_forward_halo"]


def _shard_fn(
    params,
    cfg: GNNConfig,
    axis,
    x,  # [1, Nl, F]
    send_idx,  # [1, P, Smax]
    e_src,  # [1, Emax] into [0, Nl + P*Smax)
    e_dst,  # [1, Emax] into [0, Nl)
    e_mask,  # [1, Emax]
    *,
    avg_log_degree: float,
):
    x, send_idx = x[0], send_idx[0]
    e_src, e_dst, e_mask = e_src[0], e_dst[0], e_mask[0]
    nl = x.shape[0]

    h = mlp_apply(params["encode"], x)
    deg = jax.ops.segment_sum(
        e_mask.astype(jnp.float32), e_dst, num_segments=nl
    )
    logd = jnp.log1p(deg)[:, None]
    scaler_fns = {
        "identity": lambda a: a,
        "amplification": lambda a: a * (logd / avg_log_degree),
        "attenuation": lambda a: a * (avg_log_degree / jnp.maximum(logd, 1e-6)),
    }
    for layer in params["layers"]:
        halo = halo_gather(h, send_idx, axis=axis)  # [P*Smax, d]
        h_ext = jnp.concatenate([h, halo], axis=0)
        m = mlp_apply(layer["msg"], h_ext)[e_src]
        aggs = []
        for kind in cfg.extra["aggregators"]:
            a = segment_reduce(m, e_dst, nl, kind, mask=e_mask)
            for s in cfg.extra["scalers"]:
                aggs.append(scaler_fns[s](a))
        h = h + mlp_apply(layer["post"], jnp.concatenate(aggs, axis=-1))
        h = layer_norm(h)
    return mlp_apply(params["decode"], h)[None]


def pna_forward_halo(
    params,
    cfg: GNNConfig,
    mesh: Mesh,
    xs: jax.Array,  # [P, Nl, F] shard-major node features
    send_idx: jax.Array,  # [P, P, Smax]
    edge_src_ext: jax.Array,  # [P, Emax]
    edge_dst_loc: jax.Array,  # [P, Emax]
    edge_mask: jax.Array,  # [P, Emax]
    *,
    axis=None,  # mesh axes to shard over (default: all)
    avg_log_degree: float = 2.0,
) -> jax.Array:
    """Returns [P, Nl, d_out] shard-major node outputs."""
    axis = axis if axis is not None else tuple(mesh.axis_names)
    spec = P(axis)
    fn = partial(
        _shard_fn, params, cfg, axis, avg_log_degree=avg_log_degree
    )
    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return sharded(xs, send_idx, edge_src_ext, edge_dst_loc, edge_mask)
