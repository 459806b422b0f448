"""The committed Graph500 kernel 3 cell, ``graph500-s20w.sssp8``, cut small
(``conftest.small_cell``: scale 8, interpret-mode kernels, 16 keys): it
runs correct on [0, 1) weights, zeros included, reports ``teps`` and
``relax_passes.teps``, its bf16 control fails ``dist_rel_err``, and each
fault of a weighted traversal makes ``correct`` false."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench import calibrate, graphs, harness
from bench import trace as tr
from bench.tests.conftest import run_small, small_cell
from repro.graph.session import GraphSession

CELL = "graph500-s20w.sssp8"
SEED = 2**32 + 7


def _window(cell):
    setup = harness.set_up(cell)
    pool = harness.key_pool(cell, setup.degree)
    done, _ = harness.run_window(setup, pool, SEED, 0.05, harness.no_annotation)
    return setup, done


def test_cell_is_correct_and_reports_teps():
    result = run_small(CELL)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 16
    assert result["metrics"]["teps"]["value"] > 0
    assert result["checks"]["fixpoint_violations"]["value"] == 0
    assert result["checks"]["dist_rel_err"]["value"] < 1e-5


def test_traced_cell_reports_relax_passes(monkeypatch):
    """On the chip the trace holds the window's device work; on the CPU it
    holds none, so the test gives the reduction a busy device."""
    plain = tr.reduce

    def busy(ops, spans):
        reduced = plain(ops, spans)
        return dataclasses.replace(reduced, busy_s=reduced.window_s)

    monkeypatch.setattr(tr, "reduce", busy)
    result = run_small(CELL, trace=True)
    assert result["correct"] is True
    passes = result["metrics"]["relax_passes.teps"]
    assert passes["unit"] == "passes" and passes["value"] >= 2


def test_relax_passes_sums_local_and_remote_passes():
    """Batch one: supersteps 3 and 2, so the batch makes 3 remote passes
    and max(2, 3) + max(1, 2) + max(4, 0) = 9 local ones: 12.  Batch two:
    one superstep of one closure pass: 2.  The mean is 7."""
    reader = harness.load_reader("relax_passes.teps")

    def batch(n_supersteps, inner_iters):
        return harness.Batch(np.zeros(2), np.zeros((2, 4)), np.array(n_supersteps),
                             np.array(inner_iters), np.zeros(2), 1.0)

    batches = [batch([3, 2], [[2, 1, 4, 0], [3, 2, 0, 9]]),
               batch([1, 1], [[1, 0, 0, 0], [1, 0, 0, 0]])]
    busy = tr.Reduced(window_s=1.0, busy_s=0.5, kernel_s=0.2, n_devices=1,
                      device_busy_s=[0.5], collective_s=0.0, device_ops=[],
                      idle_gaps=[])
    ctx = harness.Context(n=4, n_edges=4, batches=batches, window_s=1.0,
                          setup_s=0.0, setup_seconds={}, relax_bytes=0, trace=busy)
    assert reader(ctx) == 7.0
    assert reader(dataclasses.replace(ctx, trace=None)) is None
    idle = dataclasses.replace(busy, busy_s=0.0)
    assert reader(dataclasses.replace(ctx, trace=idle)) is None


def test_bf16_control_fails_dist_rel_err():
    cell = small_cell(CELL)
    setup, done = _window(cell)
    values, failed = harness.compare(cell, setup, done)
    assert values["dist_rel_err"] <= cell.limits["dist_rel_err"] and failed == 0
    control, failed = harness.compare(
        cell, setup, calibrate.control_batches(cell, setup, done))
    assert control["dist_rel_err"] > cell.limits["dist_rel_err"]
    assert failed == sum(b.keys.size for b in done)


def test_zero_weight_arc_runs_correct(monkeypatch):
    """Graph500 draws weights from [0, 1).  Where the small graph's hashed
    weights hold none below 2^-20, the edge from a key to its first
    neighbour weighs exactly 0 (both arcs), so the run takes a 0 edge."""
    cell = small_cell(CELL)
    n, src, dst, weights = graphs.make_arcs(cell.config)
    if not (weights < 2.0**-20).any():
        pool = harness.key_pool(cell, np.bincount(src, minlength=n))
        key = int(pool[0, 0])
        nbr = int(dst[np.flatnonzero(src == key)[0]])
        plain = graphs.hash_weights

        def with_zero(src, dst, n, low, high):
            w = plain(src, dst, n, low, high)
            w[((src == key) & (dst == nbr)) | ((src == nbr) & (dst == key))] = 0.0
            return w

        monkeypatch.setattr(graphs, "hash_weights", with_zero)
    setup, done = _window(cell)
    assert (setup.weights < 2.0**-20).any()
    _, dist = harness.stacked(done)
    assert ((dist == 0).sum(axis=1) > 1).any()  # a key's zero-weight neighbour
    values, failed = harness.compare(cell, setup, done)
    assert failed == 0
    assert all(v <= cell.limits[k] for k, v in values.items())


# -- faults ---------------------------------------------------------------------


def _one_distance_altered(session, program, sources, res, first):
    """One reached vertex one float32 step further away than computed."""
    dist = res.dist.copy()
    row = dist[0]
    v = int(np.flatnonzero(np.isfinite(row) & (row > 0))[0])
    row[v] = np.nextafter(row[v], np.float32(np.inf))
    return res._replace(dist=dist)


def _first_batch_left_at_init(session, program, sources, res, first):
    if not first:
        return res
    state, _ = program.init(session.pg, np.asarray(list(sources)))
    return res._replace(dist=state)


def _one_superstep_short(session, program, sources, res, first):
    """The state before the last superstep that changed it, taken through
    ``run_window`` from the batch's initial state."""
    k = int(np.max(res.n_supersteps))
    for short in range(k - 1, -1, -1):
        state = session.init_state(list(sources), program=program)
        if short:
            state = session.run_window(state, short, program=program).state
        dist = np.asarray(state.dist)
        if not np.array_equal(dist, res.dist):
            return res._replace(dist=dist)
    raise AssertionError("no superstep changed the state")


FAULTS = {"one_distance_altered": _one_distance_altered,
          "first_batch_left_at_init": _first_batch_left_at_init,
          "one_superstep_short": _one_superstep_short}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    plain_run = GraphSession.run
    calls = []

    def broken(self, program=None, sources=(0,)):
        res = plain_run(self, program, sources)
        calls.append(sources)
        return FAULTS[fault](self, program, sources, res, len(calls) == 1)

    monkeypatch.setattr(GraphSession, "run", broken)
    result = run_small(CELL)
    assert result["correct"] is False and result["failed"] > 0
