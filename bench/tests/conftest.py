"""Small cells for the benchmark's CPU tests: the committed cells' files,
cut to a size the CPU runs in seconds, with interpret-mode kernels.

``SSSP`` is a cell of the tests alone: the ``sssp`` program kind and
``bench/traffic/sssp8.json`` on the Graph500 configuration with hashed
weights, so the weighted path keeps its controls and faults checked until
a committed cell drives it.

A cell on more than one chip runs in a child with as many forced host
devices (``mesh_subprocess``, marked ``mesh``)."""

from __future__ import annotations

import copy
import time

import pytest

from bench import harness, load_module
from repro.testing.forced_devices import run_forced_devices

SMALL = {"kronecker": {"scale": 8}}
SSSP = "graph500-s8w.sssp8"
SSSP_LIMITS = {"dist_rel_err": 1e-3, "fixpoint_violations": 0,
               "edge_count_mismatch": 0}


def _sssp_cell() -> harness.Cell:
    cell = harness.load_cell("graph500-s20.bfs8")
    cell.name = SSSP
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"]["weights"] = [1.0, 4.0]
    cell.traffic = harness.load_json(harness.ROOT, "bench", "traffic", "sssp8.json")
    cell.kind = load_module("programs", "sssp")
    cell.limits = dict(SSSP_LIMITS)
    return cell


def small_cell(workload: str, root: str = harness.ROOT) -> harness.Cell:
    cell = _sssp_cell() if workload == SSSP else harness.load_cell(workload, root)
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"].update(SMALL[cell.config["graph"]["generator"]])
    cell.config["engine"]["backend"] = "pallas-interpret"
    if "keys" in cell.traffic:
        cell.traffic = dict(cell.traffic, keys=16)
    return cell


def run_small(workload: str, *, trace: bool = False, seed: int = 2**31 + 11,
              root: str = harness.ROOT, cell: harness.Cell | None = None) -> dict:
    """One run of a small cell on the CPU, the harness's look for a chip
    skipped; returns the result line's object."""
    cell = cell or small_cell(workload, root)
    out = harness.run(workload, seed, 0.05, trace, time.perf_counter(),
                      root=root, cell=cell, check_device=False)
    return out["result"]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "mesh: multi-device test; runs a child process with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N",
    )


@pytest.fixture(scope="session")
def mesh_subprocess():
    """``run_forced_devices``: a script run under N forced host devices."""
    return run_forced_devices
