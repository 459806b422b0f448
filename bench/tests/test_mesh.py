"""The 4-chip cell on 4 forced host devices, at a size the CPU holds: its
runs are correct and give the answers the cell gives on one device, its
control fails its limits, and each fault it can have, planted under the
timed path, makes ``correct`` false.  One child runs them all
(``_mesh_child.py``).  On one chip with the s20 configuration's graph and
partitioner, the cell answers as the s20 cell does."""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import small_cell

MESH = "graph500-s22.mesh4.bfs8"
CHILD = os.path.join(os.path.dirname(__file__), "_mesh_child.py")


@pytest.fixture(scope="module")
def child(mesh_subprocess):
    return json.loads(mesh_subprocess(CHILD, n_devices=4).strip().splitlines()[-1])


@pytest.mark.mesh
@pytest.mark.parametrize("run", ["plain", "traced"])
def test_mesh_cell_is_correct_on_four_devices(child, run):
    result = child[run]
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"] == {"hop_mismatch": {"value": 0, "limit": 0},
                                "edge_count_mismatch": {"value": 0, "limit": 0}}
    assert result["device"]["count"] == 4
    cell = small_cell(MESH)
    if run == "plain":
        assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    else:  # on the CPU the trace holds no device op: only host readings
        assert set(result["metrics"]) == {"start_s", "partition_s", "layout_s",
                                          "compile_s"}


@pytest.mark.mesh
def test_mesh_answers_equal_one_device(child):
    assert child["equal_to_one_device"] is True


@pytest.mark.mesh
def test_mesh_layout_counts_every_arc_once(child):
    assert child["arcs"]["mesh"] == child["arcs"]["partitioned"]


@pytest.mark.mesh
def test_mesh_control_fails_and_program_passes(child):
    assert child["program"] == {"hop_mismatch": 0, "edge_count_mismatch": 0}
    assert child["control"]["hop_mismatch"] > 0
    assert child["control_failed"] == child["attempted"]


@pytest.mark.mesh
@pytest.mark.parametrize("fault", ["unchanged", "half_left_out", "answer_altered",
                                   "exchange_left_out"])
def test_mesh_fault_makes_the_run_incorrect(child, fault):
    result = child["faults"][fault]
    assert result["correct"] is False and result["failed"] > 0


def _first_pass(cell):
    setup = harness.set_up(cell)
    pool = harness.key_pool(cell, setup.degree)
    done, _ = harness.run_window(setup, pool, 2**31 + 29, 0.05, harness.no_annotation)
    return setup, done[:len(pool)]


def test_one_chip_bfs_grow_answers_as_the_s20_cell():
    s20 = small_cell("graph500-s20.bfs8")
    cell = small_cell(MESH)
    cell = dataclasses.replace(cell, chips=1, config=dict(
        cell.config, graph=s20.config["graph"], partition=s20.config["partition"]))
    want_setup, want = _first_pass(s20)
    got_setup, got = _first_pass(cell)
    assert np.array_equal(got_setup.session.pg.part_of_vertex,
                          want_setup.session.pg.part_of_vertex)
    assert (got_setup.local_arcs, got_setup.remote_arcs) == (
        want_setup.local_arcs, want_setup.remote_arcs)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert np.array_equal(a.keys, b.keys) and np.array_equal(a.dist, b.dist)
