"""The command's contract on the CPU: it refuses to run without an
accelerator, and a run's last line carries exactly the result's keys,
with the compared numbers last."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import SSSP, run_small, small_cell

CELLS = ("graph500-s20.bfs8", SSSP, "graph500-s20.pagerank")
ARGS = ["--workload", "graph500-s20.bfs8", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run_cpu(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_accelerator_exits_non_zero_with_no_result():
    proc = _run_cpu(harness.ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs an accelerator" in proc.stderr


def test_a_checkout_without_the_program_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run_cpu(str(tmp_path))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_result_line_keys(workload, trace):
    result = run_small(workload, trace=trace)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(result) == keys + ["checks"]
    assert result["correct"] is True and result["failed"] == 0
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    device |= {"busy_s", "window_s"} if trace else set()
    assert set(result["device"]) == device
    cell = small_cell(workload)
    wanted = cell.per_layer if trace else cell.end_to_end
    # on the CPU the trace holds no device op, so its readers find nothing
    readable = {"start_s", "partition_s", "layout_s", "compile_s"} if trace else None
    names = {m["name"] for m in wanted}
    assert set(result["metrics"]) == (names & readable if trace else names)
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert set(result["checks"]) == set(cell.limits)
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
