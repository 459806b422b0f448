"""Graph substrate: the GoFFish-analogue subgraph-centric engine.

Layers:
  structs     -- PartitionedGraph container, WCC subgraph labeling, and the
                 static dst-sorted CsrEdgeLayout (per-tile dst ranges for the
                 block-skipping relax kernel)
  generators  -- synthetic graphs matched to the paper's dataset families
                 (plus seeded deterministic edge weights for SSSP)
  partition   -- hash + BFS-grow (METIS-like) partitioners and the
                 partition-aware local/remote edge layout (plus the
                 mesh-aware per-device layout, ``mesh_edge_layout``)
  program     -- the VertexProgram algebra (BFS / weighted SSSP / WCC /
                 PageRank) both engines are parameterized by
  traversal   -- device-resident multi-source BSP engine (whole traversal in
                 one lax.while_loop) + the per-superstep fn for the executor;
                 ``EngineConfig(mesh=)`` shards the partition axis over a
                 device mesh
  mesh_exchange -- the shard_map window program: per-destination aggregation
                 + all-to-all remote exchange, physical shard placement
  bsp         -- host drivers building BSP work traces (one bulk transfer
                 per traversal batch)
  sampler     -- fanout neighbor sampler for minibatch GNN training
  config      -- ``EngineConfig``, the one frozen knob surface every
                 engine-shaped constructor accepts (the only way to set an
                 engine knob)
  deltas      -- streaming edge mutations: bounded ``EdgeDeltaBuffer``
                 merged into the static layouts at window boundaries,
                 byte-identical to a from-scratch build
  session     -- ``open_session(pg, config)``: the unified facade over
                 engines, windowed traversal, and delta merges

**Report stability contract.**  ``TraversalResult.asdict()``,
``ExecutionReport.asdict()`` and ``ServiceReport.asdict()`` all return the
shared schema-versioned dict shape from ``graph.config.versioned_report``:
``{"schema_version": N, "kind": <report kind>, <field>: <value>, ...}``.
Consumers must key on **field names**, never positional order -- each of
these types has historically grown fields, and will again.  Adding a field
is backward compatible and does not bump ``REPORT_SCHEMA_VERSION``; renaming
or removing one does.  The ``kind`` strings (``"traversal_result"``,
``"execution_report"``, ``"service_report"``) are stable identifiers.
"""

from repro.graph.config import REPORT_SCHEMA_VERSION, EngineConfig
from repro.graph.structs import Graph, MeshEdgeLayout, PartitionedGraph
from repro.graph.generators import rmat_graph, road_grid_graph, erdos_renyi_graph
from repro.graph.partition import (
    bfs_grow_partition,
    contiguous_device_map,
    hash_partition,
    mesh_edge_layout,
)
from repro.graph.program import (
    BUILTIN_PROGRAMS,
    BfsProgram,
    PageRankProgram,
    SsspProgram,
    VertexProgram,
    WccProgram,
)

__all__ = [
    "Graph",
    "MeshEdgeLayout",
    "PartitionedGraph",
    "rmat_graph",
    "road_grid_graph",
    "erdos_renyi_graph",
    "hash_partition",
    "bfs_grow_partition",
    "contiguous_device_map",
    "mesh_edge_layout",
    "VertexProgram",
    "BfsProgram",
    "SsspProgram",
    "WccProgram",
    "PageRankProgram",
    "BUILTIN_PROGRAMS",
    "EngineConfig",
    "REPORT_SCHEMA_VERSION",
    "EdgeDeltaBuffer",
    "apply_delta_buffer",
    "GraphSession",
    "open_session",
]

_LAZY = {
    # jax-importing modules: resolved on first attribute access so that
    # ``import repro.graph`` stays cheap for host-only consumers
    "EdgeDeltaBuffer": ("repro.graph.deltas", "EdgeDeltaBuffer"),
    "apply_delta_buffer": ("repro.graph.deltas", "apply_delta_buffer"),
    "GraphSession": ("repro.graph.session", "GraphSession"),
    "open_session": ("repro.graph.session", "open_session"),
}


def __getattr__(name: str):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module 'repro.graph' has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod_name), attr)
