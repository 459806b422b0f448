"""The 4-chip cell at a size the CPU holds, on 4 forced host devices (run
by ``test_mesh.py`` through the ``mesh_subprocess`` fixture).

Prints one JSON object: the result lines of a plain and a traced run of
the small cell, whether its answers equal those of the same cell on one
device, its layout's arc counts, its control's and the program's numbers,
and the result line of a run with each fault planted under the timed
path."""

import dataclasses
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import calibrate, harness  # noqa: E402
from bench.tests import test_faults  # noqa: E402
from bench.tests.conftest import run_small, small_cell  # noqa: E402
from repro.graph.partition import partitioned_edge_layout  # noqa: E402
from repro.graph.session import GraphSession  # noqa: E402

MESH = "graph500-s22.mesh4.bfs8"
SEED = 2**31 + 23

assert len(jax.devices()) == 4, jax.devices()


def window(cell):
    setup = harness.set_up(cell)
    pool = harness.key_pool(cell, setup.degree)
    done, _ = harness.run_window(setup, pool, SEED, 0.05, harness.no_annotation)
    return setup, done[:len(pool)]  # the first pass: the same on every path


def result_fault(name):
    """A run with ``GraphSession.run`` broken as ``test_faults`` breaks it."""
    plain = GraphSession.run

    def broken(self, program=None, sources=(0,)):
        res = plain(self, program, sources)
        return test_faults.RESULT_FAULTS[name](
            res, test_faults._initial(self, program, sources))

    GraphSession.run = broken
    try:
        return run_small(MESH, seed=SEED)
    finally:
        GraphSession.run = plain


def no_exchange(x, axis_name, split_axis, concat_axis, tiled=False, **_):
    """An all_to_all that delivers only each device's block to itself: what
    crosses between chips never arrives (the min identity in its place)."""
    own = jnp.arange(x.shape[split_axis]) == jax.lax.axis_index(axis_name)
    shape = [1] * x.ndim
    shape[split_axis] = -1
    return jnp.where(own.reshape(shape), x, jnp.full_like(x, jnp.inf))


def exchange_fault():
    plain = jax.lax.all_to_all
    jax.lax.all_to_all = no_exchange
    try:
        return run_small(MESH, seed=SEED)
    finally:
        jax.lax.all_to_all = plain


def main():
    out = {"plain": run_small(MESH, seed=SEED),
           "traced": run_small(MESH, trace=True, seed=SEED)}

    mesh = small_cell(MESH)
    one = dataclasses.replace(mesh, chips=1)
    mesh_setup, mesh_done = window(mesh)
    one_setup, one_done = window(one)
    out["equal_to_one_device"] = all(
        np.array_equal(a.keys, b.keys) and np.array_equal(a.dist, b.dist)
        for a, b in zip(mesh_done, one_done, strict=True))
    layout = partitioned_edge_layout(one_setup.session.pg)
    out["arcs"] = {"mesh": [mesh_setup.local_arcs, mesh_setup.remote_arcs],
                   "partitioned": [layout.local.n_edges, layout.remote.n_edges]}
    out["program"], _ = harness.compare(mesh, mesh_setup, mesh_done)
    out["control"], out["control_failed"] = harness.compare(
        mesh, mesh_setup, calibrate.control_batches(mesh, mesh_setup, mesh_done))
    out["attempted"] = sum(b.keys.size for b in mesh_done)

    out["faults"] = {name: result_fault(name) for name in test_faults.RESULT_FAULTS}
    out["faults"]["exchange_left_out"] = exchange_fault()
    print(json.dumps(out))


if __name__ == "__main__":
    t = time.perf_counter()
    main()
    print(f"mesh child: {time.perf_counter() - t:.1f} s", file=sys.stderr)
