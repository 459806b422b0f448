"""Elastic executor integration tests."""

import math

import numpy as np
import pytest

from repro.core import TimeFunction, ffd_placement, mfp_placement, default_placement
from repro.core.elastic import ElasticBSPExecutor
from repro.graph import bfs_grow_partition, erdos_renyi_graph, road_grid_graph
from repro.graph.bsp import run_sssp
from repro.graph.config import EngineConfig
from repro.graph.traversal import reference_bfs


def _plan_from_trace(pg, source, strategy):
    _, trace = run_sssp(pg, source)
    tf = TimeFunction.from_trace(trace)
    return strategy(tf), tf


def test_executor_distances_correct_under_any_plan():
    g = erdos_renyi_graph(300, 5.0, seed=21)
    pg = bfs_grow_partition(g, 4, seed=1)
    ref = reference_bfs(pg, 0)
    ex = ElasticBSPExecutor(pg)
    for strategy in (default_placement, ffd_placement, mfp_placement):
        plan, _ = _plan_from_trace(pg, 0, strategy)
        rep = ex.run(0, plan)
        np.testing.assert_allclose(rep.dist, ref)
        assert rep.cost.cost_quanta >= 1


def test_pinned_plan_causes_no_migrations():
    g = road_grid_graph(25, 25, seed=2)
    pg = bfs_grow_partition(g, 6, seed=3)
    ex = ElasticBSPExecutor(pg)
    plan, _ = _plan_from_trace(pg, 0, mfp_placement)
    rep = ex.run(0, plan)
    assert rep.n_migrations == 0


def test_ffd_plan_may_migrate_but_executes():
    g = road_grid_graph(25, 25, seed=2)
    pg = bfs_grow_partition(g, 6, seed=3)
    ex = ElasticBSPExecutor(pg)
    plan, tf = _plan_from_trace(pg, 0, ffd_placement)
    rep = ex.run(0, plan)
    assert rep.n_supersteps == tf.n_supersteps


def test_replan_recovers_from_bad_prediction():
    """Feed the executor a plan for the wrong source; dynamic re-planning
    (beyond-paper, the paper's s7 future work) must still execute correctly."""
    g = erdos_renyi_graph(400, 4.0, seed=5)
    pg = bfs_grow_partition(g, 5, seed=6)
    wrong_source = 7
    real_source = 200
    plan, _ = _plan_from_trace(pg, wrong_source, ffd_placement)
    ex = ElasticBSPExecutor(pg)
    rep = ex.run(real_source, plan, strategy_fn=ffd_placement, replan=True)
    ref = reference_bfs(pg, real_source)
    np.testing.assert_allclose(rep.dist, ref)
    assert rep.replans >= 1


def test_single_divergence_triggers_exactly_one_replan():
    """Regression for the one-row splice bug: the old replan path rebuilt the
    plan with s+1 rows, so every subsequent superstep re-triggered a replan.
    The online re-planner splices the full extrapolated horizon, so one
    observed divergence costs exactly one replan."""
    g = erdos_renyi_graph(400, 4.0, seed=5)
    pg = bfs_grow_partition(g, 5, seed=6)
    wrong_source, real_source = 7, 200
    assert pg.part_of_vertex[wrong_source] != pg.part_of_vertex[real_source]
    plan, _ = _plan_from_trace(pg, wrong_source, ffd_placement)
    for window in (1, 4):
        ex = ElasticBSPExecutor(pg, config=EngineConfig(window=window))
        rep = ex.run(
            real_source, plan, strategy_fn=ffd_placement, replan=True,
        )
        np.testing.assert_allclose(rep.dist, reference_bfs(pg, real_source))
        assert rep.replans == 1, f"window={window}: {rep.replans} replans"


@pytest.mark.parametrize(
    "seed,n_parts", [(21, 4), (5, 3), (9, 6)]
)
def test_windowed_execution_matches_per_superstep_path(seed, n_parts):
    """Window boundaries must not change the math: identical dist and summed
    work counters for k in {1, 4, 16}."""
    g = erdos_renyi_graph(300, 5.0, seed=seed)
    pg = bfs_grow_partition(g, n_parts, seed=1)
    plan, tf = _plan_from_trace(pg, 0, ffd_placement)
    base = ElasticBSPExecutor(pg, config=EngineConfig(window=1)).run(0, plan)
    np.testing.assert_allclose(base.actual_tau.tau, tf.tau)
    for k in (4, 16):
        rep = ElasticBSPExecutor(pg, config=EngineConfig(window=k)).run(0, plan)
        np.testing.assert_array_equal(rep.dist, base.dist)
        np.testing.assert_array_equal(rep.actual_tau.tau, base.actual_tau.tau)
        assert rep.n_supersteps == base.n_supersteps


def test_windowed_host_sync_budget():
    """k=8 must cost <= ceil(S/8) + 1 bulk pulls (windows + final dist)."""
    g = road_grid_graph(25, 25, seed=2)  # long-diameter graph, many supersteps
    pg = bfs_grow_partition(g, 6, seed=3)
    plan, tf = _plan_from_trace(pg, 0, ffd_placement)
    ex = ElasticBSPExecutor(pg, config=EngineConfig(window=8))
    rep = ex.run(0, plan)
    assert rep.n_supersteps == tf.n_supersteps
    assert rep.host_syncs <= math.ceil(rep.n_supersteps / 8) + 1


def test_migration_bytes_priced_into_billed_makespan():
    """A migrating plan must report moved bytes and bill the transfer time
    (bytes / move_bandwidth) into the receiving VM's busy time; a pinned
    plan on the same workload reports zero."""
    g = road_grid_graph(25, 25, seed=2)
    pg = bfs_grow_partition(g, 6, seed=3)
    ex = ElasticBSPExecutor(pg)

    plan, _ = _plan_from_trace(pg, 0, ffd_placement)
    rep = ex.run(0, plan)
    assert rep.n_migrations > 0  # ffd migrates on this workload
    assert rep.migration_bytes > 0
    # pricing: billed migration seconds == moved bytes / staging bandwidth
    assert rep.cost.migration_secs == pytest.approx(
        rep.migration_bytes / ex.billing.move_bandwidth
    )
    assert rep.migration_secs == rep.cost.migration_secs
    # makespan can only grow relative to the migration-free lower bound
    assert rep.cost.makespan >= rep.actual_tau.t_min() - 1e-12

    pinned, _ = _plan_from_trace(pg, 0, mfp_placement)
    rep_pin = ex.run(0, pinned)
    assert rep_pin.n_migrations == 0
    assert rep_pin.migration_bytes == 0
    assert rep_pin.cost.migration_secs == 0.0


def test_moves_scheduled_past_convergence_are_not_counted():
    """A plan tail that moves partitions *after* the traversal converges must
    not count or bill those moves, even when the tail rows share the final
    window with executed supersteps."""
    from repro.core.placement import Placement

    g = erdos_renyi_graph(300, 5.0, seed=21)
    pg = bfs_grow_partition(g, 4, seed=1)
    plan, tf = _plan_from_trace(pg, 0, ffd_placement)
    # extend the schedule 8 rows past convergence, shuffling every partition
    # onto a different VM each phantom superstep
    extra_vm = np.tile(
        (np.arange(pg.n_parts, dtype=np.int64)[None] + 1) % pg.n_parts, (8, 1)
    )
    padded = Placement(
        strategy=plan.strategy,
        tau=np.vstack([plan.tau, np.zeros((8, pg.n_parts))]),
        vm_of=np.vstack([plan.vm_of, extra_vm]),
    )
    ex = ElasticBSPExecutor(pg, config=EngineConfig(window=16))
    base = ex.run(0, plan)
    rep = ex.run(0, padded)  # whole run + tail in one window
    assert rep.n_supersteps == base.n_supersteps
    assert rep.n_migrations == base.n_migrations
    assert rep.migration_bytes == base.migration_bytes
    assert rep.cost.migration_secs == pytest.approx(
        rep.migration_bytes / ex.billing.move_bandwidth
    )


def test_relayout_is_noop_on_the_dense_path():
    """relayout=True without a mesh engine must change nothing: one device
    does all the work, so there is no compute layout to follow the plan."""
    g = erdos_renyi_graph(300, 4.0, seed=6)
    pg = bfs_grow_partition(g, 4, seed=1)
    _, trace = run_sssp(pg, 0)
    plan = ffd_placement(TimeFunction.from_trace(trace))
    base = ElasticBSPExecutor(pg, config=EngineConfig(window=2)).run(0, plan)
    rep = ElasticBSPExecutor(
        pg, config=EngineConfig(window=2, relayout=True)
    ).run(0, plan)
    np.testing.assert_array_equal(rep.dist, base.dist)
    np.testing.assert_array_equal(rep.actual_tau.tau, base.actual_tau.tau)
    assert rep.relayouts == 0
    assert rep.device_moves == base.device_moves
    assert rep.cost.migration_secs == base.cost.migration_secs
