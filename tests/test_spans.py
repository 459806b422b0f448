"""Host spans and device scopes (``repro.spans``).

Spans nest and are recorded only inside ``collect()``; the engine, the
partitioner and the layout builder open the spans the benchmark's trace
reduction labels idle gaps with; ``scope_of`` reads a name stack back to
its plane and role; and ``TraversalEngine.window_hlo`` gives the HLO of the
very program a launch runs, with every scope in its ``op_name`` metadata.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

import jax

from repro import spans
from repro.graph.config import EngineConfig
from repro.graph.generators import rmat_graph
from repro.graph.partition import bfs_grow_partition, partitioned_edge_layout
from repro.graph.program import BfsProgram, PageRankProgram
from repro.graph.traversal import TraversalEngine

_CHILD = os.path.join(os.path.dirname(__file__), "_spans_mesh_child.py")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _names(records):
    return [name for name, _, _ in records]


def test_spans_nest_and_record_in_collect():
    with spans.collect() as records:
        with spans.span("outer"):
            with spans.span("outer.inner"):
                pass
            with spans.span("outer.inner"):
                pass
    assert _names(records) == ["outer.inner", "outer.inner", "outer"]
    (_, o0, o1) = records[2]
    assert all(o0 <= t0 <= t1 <= o1 for _, t0, t1 in records[:2])
    totals = spans.span_seconds(records)
    assert list(totals) == ["outer", "outer.inner"]
    assert totals["outer.inner"] == pytest.approx(
        sum(t1 - t0 for _, t0, t1 in records[:2]))


def test_nothing_is_recorded_outside_collect():
    with spans.span("before"):
        pass
    with spans.collect() as records:
        pass
    with spans.span("after"):
        pass
    assert records == []
    assert spans._SINK.get() is None


def test_inner_collect_takes_its_own_spans():
    with spans.collect() as outer:
        with spans.span("a"):
            with spans.collect() as inner:
                with spans.span("b"):
                    pass
    assert _names(inner) == ["b"] and _names(outer) == ["a"]


def test_span_records_even_when_the_block_raises():
    with spans.collect() as records:
        with pytest.raises(ValueError):
            with spans.span("fails"):
                raise ValueError("boom")
    assert _names(records) == ["fails"]


@pytest.mark.parametrize("names,want", [
    (("local", "gather"), ("local", "gather")),
    (("while", "body", "remote", "vmap()", "counters"), ("remote", "counters")),
    (("local", "frontier", "collective"), ("local", "collective")),
    (("remote", "collective"), ("remote", "collective")),
    (("finish",), ("finish", "finish")),
    (("finish", "vmap()", "collective"), ("finish", "collective")),
    ((), None),
    (("while", "body"), None),
    (("gather",), None),
    (("local",), None),
    (("local", "gather", "relax"), None),
    (("local", "remote", "gather"), None),
    (("local", "collective", "collective"), None),
    (("finish", "gather"), None),
])
def test_scope_of(names, want):
    assert spans.scope_of(names) == want


@pytest.fixture(scope="module")
def pg():
    return bfs_grow_partition(rmat_graph(8, 8, seed=5), 4, seed=1)


def test_program_spans_in_set_up_and_run():
    g = rmat_graph(7, 8, seed=2)
    with spans.collect() as records:
        pgx = bfs_grow_partition(g, 3, seed=0)
        partitioned_edge_layout(pgx)
        partitioned_edge_layout(pgx)  # cached: no second layout span
        engine = TraversalEngine(pgx, program=BfsProgram(),
                                 config=EngineConfig(backend="xla"))
        engine.run([0, 1])
    names = _names(records)
    for name in ("graph.partition", "graph.partition.seeds",
                 "graph.partition.grow", "graph.partition.refine",
                 "graph.layout.local", "graph.layout.remote",
                 "graph.engine_build", "graph.init", "graph.launch",
                 "graph.fetch"):
        assert names.count(name) == 1, (name, names)
    assert names.count("graph.layout") == 1


def test_run_window_spans(pg):
    engine = TraversalEngine(pg, program=BfsProgram(),
                             config=EngineConfig(backend="xla"))
    state = engine.init_state([0, 3])
    with spans.collect() as records:
        engine.run_window(state, 2)
    assert _names(records) == ["graph.launch", "graph.fetch"]


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("program", [BfsProgram(), PageRankProgram()],
                         ids=["bfs", "pagerank"])
def test_window_hlo_is_the_launched_program_with_its_scopes(pg, program, backend):
    engine = TraversalEngine(pg, program=program,
                             config=EngineConfig(backend=backend))
    s_batch, k = 3, engine.m_max
    hlo = engine.window_hlo(s_batch, k)
    state = engine.init_state(np.arange(s_batch))
    launched = engine._window.lower(
        state.dist, state.frontier, state.n_supersteps, k, engine._consts
    ).compile().as_text()
    assert hlo == launched
    found = {spans.scope_of(op.split("/")[:-1]) for op in _OP_NAME.findall(hlo)}
    want = {(plane, role) for plane in spans.PLANES
            for role in ("gather", "relax", "counters", "frontier")}
    assert want | {("finish", "finish")} <= found
    assert not any(role == "collective" for _, role in found - {None})


@pytest.mark.mesh
def test_mesh_window_hlo_and_spans(mesh_subprocess):
    """The mesh window's HLO carries every role, collectives included, and
    a mesh ``run`` and ``run_window`` open their spans; see
    ``tests/_spans_mesh_child.py``."""
    out = mesh_subprocess(_CHILD, n_devices=2)
    assert "MESH SPANS OK" in out


def test_scopes_leave_no_trace_without_a_profiler(pg):
    """A span outside a profiler is an annotation nobody records: the
    engine runs the same with and without ``collect()``."""
    engine = TraversalEngine(pg, program=BfsProgram(),
                             config=EngineConfig(backend="xla"))
    a = jax.device_get(engine.run([1, 2]))
    with spans.collect():
        b = jax.device_get(engine.run([1, 2]))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
