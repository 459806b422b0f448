"""Each fault a cell can have, planted under the timed path of a small run
with the harness's look for a chip skipped, makes ``correct`` false: a run
that returns its state unchanged, half of the batch left out, the
exchange between partitions left out, and one answer altered where it is
produced."""

from __future__ import annotations

import numpy as np
import pytest

from bench.tests.conftest import SSSP, run_small
from repro.graph.session import GraphSession
from repro.graph.traversal import TraversalEngine

CELLS = ("graph500-s20.bfs8", SSSP, "graph500-s20.pagerank")


def _initial(session, program, sources):
    state, _ = program.init(session.pg, np.asarray(list(sources)))
    return state


def _unchanged(res, init):
    return res._replace(dist=init)


def _half_left_out(res, init):
    dist = res.dist.copy()
    half = dist.shape[0] // 2
    dist[half:] = init[half:]
    return res._replace(dist=dist)


def _answer_altered(res, init):
    dist = res.dist.copy()
    row = dist[0]
    v = int(np.flatnonzero(np.isfinite(row) & (row > 0))[0])
    row[v] = row[v] * np.float32(1.01) if row[v] < 1 else row[v] + 1
    return res._replace(dist=dist)


RESULT_FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out,
                 "answer_altered": _answer_altered}


#: a PageRank run has one row, so it has no half to leave out
CASES = [(w, f) for w in CELLS for f in sorted(RESULT_FAULTS)
         if not (f == "half_left_out" and w.endswith("pagerank"))]


@pytest.mark.parametrize("workload,fault", CASES)
def test_result_fault_makes_the_run_incorrect(workload, fault, monkeypatch):
    plain_run = GraphSession.run

    def broken(self, program=None, sources=(0,)):
        res = plain_run(self, program, sources)
        return RESULT_FAULTS[fault](res, _initial(self, program, sources))

    monkeypatch.setattr(GraphSession, "run", broken)
    result = run_small(workload)
    assert result["correct"] is False and result["failed"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_exchange_left_out_makes_the_run_incorrect(workload, monkeypatch):
    plain_init = TraversalEngine.__init__

    def no_exchange(self, *args, **kwargs):
        plain_init(self, *args, **kwargs)
        self._relax_r = lambda blockmap, cand, base: base  # remote pass skipped

    monkeypatch.setattr(TraversalEngine, "__init__", no_exchange)
    result = run_small(workload)
    assert result["correct"] is False and result["failed"] > 0
