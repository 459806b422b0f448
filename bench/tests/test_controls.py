"""The control of each cell, at a size a test run holds: the reference in
the program's place, in the precision below float32 (SSSP, PageRank) or
with BFS's guarantee broken (each key's deepest level left unreached),
fails the cell's limits, while the program passes them."""

from __future__ import annotations

import numpy as np
import pytest

from bench import calibrate, harness
from bench.tests.conftest import SSSP, small_cell

CELLS = ("graph500-s20.bfs8", SSSP, "graph500-s20.pagerank")


def _fails(cell, values) -> list:
    return [k for k, v in values.items() if v > cell.limits[k]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(workload):
    cell = small_cell(workload)
    setup = harness.set_up(cell)
    pool = harness.key_pool(cell, setup.degree)
    done, _ = harness.run_window(setup, pool, 2**33 + 1, 0.05, harness.no_annotation)
    program, failed = harness.compare(cell, setup, done)
    assert _fails(cell, program) == [] and failed == 0
    control, failed = harness.compare(
        cell, setup, calibrate.control_batches(cell, setup, done)
    )
    assert _fails(cell, control) != []
    assert failed == sum(b.keys.size for b in done)


def test_bfs_precision_control_cannot_fail():
    """Why BFS's control breaks a guarantee instead: hop counts are small
    integers, exact in bfloat16."""
    import jax.numpy as jnp

    hops = np.arange(0, 257, dtype=np.float32)
    assert np.array_equal(np.asarray(jnp.asarray(hops).astype(jnp.bfloat16),
                                     np.float32), hops)
