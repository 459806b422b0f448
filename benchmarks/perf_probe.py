"""Hillclimb probe: lower+compile one cell with config overrides and print
the roofline-relevant numbers.  Usage:

  PYTHONPATH=src python -m benchmarks.perf_probe deepseek-v3-671b prefill_32k \
      single sp_residual=True

Traversal-engine mode: lower+compile the device-resident BSP engine for a
synthetic partitioned graph and print its HLO size/memory footprint (the
whole traversal is one executable -- no per-superstep dispatch to probe):

  PYTHONPATH=src python -m benchmarks.perf_probe traversal [scale] [sources]
"""

import os
import sys

if sys.argv[1:2] != ["traversal"]:
    # the LM dry-run wants 512 fake devices; the traversal probe wants the
    # single real device (flags must be set before the first jax import)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs.registry as registry
from repro.launch.dryrun import parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_bundle


def probe(arch: str, shape: str, mesh_kind: str, overrides: dict):
    spec = registry.ARCHS[arch]
    if overrides:
        new_cfg = dataclasses.replace(spec.config, **overrides)
        registry.ARCHS[arch] = dataclasses.replace(spec, config=new_cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    b = build_bundle(arch, shape, mesh)
    state_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), b.state_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    in_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), b.input_spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    sample_out = jax.eval_shape(b.step_fn, b.abstract_state, b.abstract_inputs)
    if isinstance(sample_out, tuple):
        out_sh = (state_sh, jax.tree.map(lambda _: NamedSharding(mesh, P()), sample_out[1]))
    else:
        out_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), sample_out)
    with mesh:
        compiled = (
            jax.jit(
                b.step_fn,
                in_shardings=(state_sh, in_sh),
                out_shardings=out_sh,
                donate_argnums=(0,) if b.donate_state else (),
            )
            .lower(b.abstract_state, b.abstract_inputs)
            .compile()
        )
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    if os.environ.get("PROBE_TOP_BUFFERS"):
        import re

        dtb = {"f64": 8, "f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "pred": 1}
        sizes: dict = {}
        for line in hlo.splitlines():
            m = re.match(r"\s*%?[\w.\-]+\s*=\s*(\w+)\[([\d,]*)\]", line)
            if not m:
                continue
            dt, dims = m.groups()
            if dt not in dtb:
                continue
            nelem = 1
            for d in dims.split(","):
                if d:
                    nelem *= int(d)
            sz = nelem * dtb[dt]
            opm = re.search(r"\]\S*\s+([a-z\-]+)\(", line)
            key = ((opm.group(1) if opm else "?"), dt + "[" + dims + "]")
            if sz > 2**26:
                tot, cnt = sizes.get(key, (0, 0))
                sizes[key] = (tot + sz, cnt + 1)
        for (op, shape), (tot, cnt) in sorted(sizes.items(), key=lambda kv: -kv[1][0])[:15]:
            print(f"  {tot/2**30:8.2f} GiB x{cnt:3d} {op:16s} {shape}")
    print(
        f"{arch}:{shape}:{mesh_kind} {overrides} -> "
        f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
        f"args={mem.argument_size_in_bytes/2**30:.2f}GiB "
        f"flops/dev={cost.get('flops', 0):.3g} "
        f"bytes/dev={cost.get('bytes accessed', 0):.3g} "
        f"coll/dev={coll['wire_bytes_per_device']/2**30:.2f}GiB "
        f"{ {k: round(v/2**30,2) for k,v in coll['by_op'].items() if v} }"
    )


def probe_traversal(scale: int = 12, n_sources: int = 16):
    """Lower + compile the device-resident traversal engine and print its
    footprint: one executable per (graph, S) covering the entire traversal."""
    from repro.graph.generators import rmat_graph
    from repro.graph.partition import bfs_grow_partition
    from repro.graph.config import EngineConfig
    from repro.graph.traversal import TraversalEngine
    import jax.numpy as jnp

    g = rmat_graph(scale, 8, seed=3)
    pg = bfs_grow_partition(g, 8, seed=1)
    eng = TraversalEngine(pg, config=EngineConfig(m_max=512))
    dist = jnp.full((n_sources, g.n_vertices), jnp.inf, jnp.float32)
    frontier = jnp.zeros((n_sources, g.n_vertices), bool)
    compiled = eng._traverse.lower(dist, frontier).compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # some backends wrap it in a list
        cost = cost[0] if cost else {}
    print(
        f"traversal: RMAT 2^{scale} x {n_sources} sources -> one executable; "
        f"temp={mem.temp_size_in_bytes/2**20:.1f}MiB "
        f"args={mem.argument_size_in_bytes/2**20:.1f}MiB "
        f"out={mem.output_size_in_bytes/2**20:.1f}MiB "
        f"flops={cost.get('flops', 0):.3g}"
    )


if __name__ == "__main__":
    if sys.argv[1:2] == ["traversal"]:
        probe_traversal(*(int(a) for a in sys.argv[2:4]))
        sys.exit(0)
    arch, shape, mesh_kind = sys.argv[1:4]
    overrides = {}
    for kv in sys.argv[4:]:
        k, v = kv.split("=")
        overrides[k] = {"True": True, "False": False}.get(v, v)
        if isinstance(overrides[k], str):
            try:
                overrides[k] = int(v)
            except ValueError:
                overrides[k] = float(v)
    probe(arch, shape, mesh_kind, overrides)
