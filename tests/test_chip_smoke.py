"""CPU rehearsal of ``chip_smoke.py``: the entry refuses to run without a
chip, its phases pass at R-MAT scale 10 with interpret-mode kernels, and
its scipy references agree with the engine's numpy oracles."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from repro.graph.generators import erdos_renyi_graph, rmat_graph, weighted  # noqa: E402
from repro.graph.partition import bfs_grow_partition  # noqa: E402
from repro.graph.traversal import (  # noqa: E402
    reference_bfs,
    reference_pagerank,
    reference_sssp,
    reference_wcc,
)

REHEARSAL_SCALE = 10
SINGLE_CHIP_PHASES = (
    "bfs", "sssp", "wcc", "pagerank", "bfs_xla", "elastic", "service",
)


def _cpu_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_entry_fails_without_a_tpu(where, tmp_path):
    """Under ``JAX_PLATFORMS=cpu`` the script exits non-zero and prints no
    ok line, in the checkout and in a directory holding only the script."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    proc = subprocess.run(
        [sys.executable, script], cwd=os.path.dirname(script),
        env=_cpu_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture(scope="module")
def scale10():
    wl = chip_smoke.build_workload(REHEARSAL_SCALE)
    return wl, chip_smoke.build_references(wl)


@pytest.fixture(scope="module")
def single_chip_records(scale10):
    import jax

    wl, refs = scale10
    phases = chip_smoke.single_chip_phases(wl, refs, backend="pallas-interpret")
    return {
        name: chip_smoke.run_phase(name, fn, jax.devices())
        for name, fn in phases
    }


@pytest.mark.parametrize("phase", SINGLE_CHIP_PHASES)
def test_single_chip_phase_passes_at_scale_10(single_chip_records, phase):
    rec = single_chip_records[phase]
    assert rec["correct"], rec.get("error", rec)
    assert rec["compile_s"] <= rec["wall_s"]
    assert rec["supersteps"] > 0


def test_kernel_records_count_the_grid(single_chip_records, scale10):
    """The kernel record's grid is the relax kernel's: source tiles x row
    blocks x t_max per pass, summed over the passes the run made."""
    rec = single_chip_records["bfs"]["kernel"]
    for plane in ("local", "remote"):
        g = rec[plane]
        assert g["grid_steps"] == g["row_blocks"] * g["t_max"]  # S=8: 1 tile
        assert 0 < g["tiles_with_edges"] <= g["row_blocks"] * g["t_max"]
    assert rec["remote_passes"] == single_chip_records["bfs"]["supersteps"]
    assert rec["local_passes"] >= rec["remote_passes"]
    assert rec["grid_steps"] == (
        rec["local_passes"] * rec["local"]["grid_steps"]
        + rec["remote_passes"] * rec["remote"]["grid_steps"]
    )
    assert "kernel" not in single_chip_records["bfs_xla"]


@pytest.mark.mesh
def test_mesh_phases_pass_on_forced_devices(mesh_subprocess):
    child = os.path.join(os.path.dirname(__file__), "_chip_smoke_mesh_child.py")
    out = mesh_subprocess(child, str(REHEARSAL_SCALE), n_devices=4)
    assert "CHIP_SMOKE_MESH_OK" in out


# -- the host references ----------------------------------------------------


def _small_graphs():
    return [
        bfs_grow_partition(erdos_renyi_graph(300, 4.0, seed=3), 4, seed=1),
        bfs_grow_partition(rmat_graph(8, 8, seed=5), 4, seed=2),
    ]


@pytest.mark.parametrize("which", ["bfs", "sssp", "wcc", "pagerank"])
def test_scipy_references_match_engine_oracles(which):
    for pg in _small_graphs():
        g = pg.graph
        sources = np.array([0, 17, g.n_vertices - 1])
        if which == "bfs":
            got = chip_smoke.scipy_bfs(g, sources)
            want = np.stack([reference_bfs(pg, int(s)) for s in sources])
            np.testing.assert_array_equal(got, want)
        elif which == "sssp":
            wpg = type(pg)(weighted(g, seed=4), pg.n_parts, pg.part_of_vertex)
            got = chip_smoke.scipy_sssp(wpg.graph, sources)
            want = np.stack([reference_sssp(wpg, int(s)) for s in sources])
            np.testing.assert_array_equal(got, want)
        elif which == "wcc":
            np.testing.assert_array_equal(
                chip_smoke.scipy_wcc(g), reference_wcc(pg)
            )
        else:
            np.testing.assert_allclose(
                chip_smoke.scipy_pagerank(g, 0.85, 12),
                reference_pagerank(pg, 0.85, 12),
                rtol=1e-12,
            )


def _float32_bellman_ford(g, source: int) -> np.ndarray:
    dist = np.full(g.n_vertices, np.inf, np.float32)
    dist[source] = 0
    w = g.edge_weights.astype(np.float32)
    while True:
        new = dist.copy()
        np.minimum.at(new, g.dst, dist[g.src] + w)
        if np.array_equal(new, dist):
            return dist
        dist = new


def test_sssp_fixpoint_check_accepts_only_the_fixpoint():
    g = weighted(erdos_renyi_graph(200, 5.0, seed=9), seed=2)
    sources = [0, 50]
    dist = np.stack([_float32_bellman_ford(g, s) for s in sources])
    assert chip_smoke.sssp_fixpoint_ok(g, sources, dist)
    v = int(np.argmax(dist[1]))
    for delta in (np.float32(-0.25), np.float32(0.25)):
        bad = dist.copy()
        bad[1, v] += delta
        assert not chip_smoke.sssp_fixpoint_ok(g, sources, bad)
    bad = dist.copy()
    bad[0, sources[0]] = 1.0  # the source must sit at 0
    assert not chip_smoke.sssp_fixpoint_ok(g, sources, bad)
