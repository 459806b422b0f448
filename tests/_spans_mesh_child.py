"""Mesh child of ``test_spans.py`` (2 forced host devices).

Asserts that the mesh window's HLO text carries every plane and role in
its ``op_name`` metadata, the ``collective`` role among them, and that a
mesh ``run`` and a ``run_window`` with a new device map open their spans.
Exit 0 and the last line ``MESH SPANS OK`` mean every assertion passed.
"""

import re

import numpy as np

import jax

from repro import spans
from repro.dist.sharding import partition_mesh
from repro.graph.config import EngineConfig
from repro.graph.generators import rmat_graph
from repro.graph.partition import bfs_grow_partition
from repro.graph.program import BfsProgram, PageRankProgram
from repro.graph.traversal import TraversalEngine

assert len(jax.devices()) == 2, f"expected 2 forced devices, got {jax.devices()}"
pg = bfs_grow_partition(rmat_graph(7, 8, seed=4), 4, seed=1)
mesh = partition_mesh(2)
roles = {(plane, role) for plane in spans.PLANES
         for role in ("gather", "relax", "counters", "frontier", "collective")}
for program in (BfsProgram(), PageRankProgram()):
    engine = TraversalEngine(pg, program=program,
                             config=EngineConfig(mesh=mesh, backend="xla"))
    hlo = engine.window_hlo(2, engine.m_max)
    found = {spans.scope_of(op.split("/")[:-1])
             for op in re.findall(r'op_name="([^"]*)"', hlo)}
    missing = (roles | {("finish", "finish"), ("finish", "collective")}) - found
    if program.stationary:
        missing.discard(("local", "collective"))  # no closure-loop sync
    assert not missing, (program.name, sorted(missing))

engine = TraversalEngine(pg, program=BfsProgram(),
                         config=EngineConfig(mesh=mesh, backend="xla"))
with spans.collect() as records:
    engine.run([0, 5])
    state = engine.init_state([0, 5])
    engine.run_window(state, 2, device_of_part=np.array([1, 1, 0, 0]))
names = [name for name, _, _ in records]
assert names == ["graph.init", "graph.launch", "graph.fetch", "graph.init",
                 "graph.relayout", "graph.launch", "graph.fetch"], names
print("MESH SPANS OK")
