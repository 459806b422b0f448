"""The trace reduction gives known numbers: on hand-made events, and on a
small trace recorded on a TPU v5e (two BFS batches of 8 keys on a scale-14
Kronecker graph, pallas backend)."""

from __future__ import annotations

import os

import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "bfs8_s14_v5e.xplane.pb")


def _op(name, start, dur, kernel=False, device="/device:TPU:0"):
    return tr.Op(device, name, start, dur, kernel)


def test_reduce_on_hand_made_events():
    spans = [
        tr.Span("bench.window", 100, 1000),
        tr.Span("bench.batch", 100, 700),
        tr.Span("bench.count", 800, 300),
    ]
    ops = [
        _op("fusion.1", 50, 150),  # clipped to [100, 200)
        _op("body.7 (relax kernel)", 200, 300, kernel=True),
        _op("fusion.2", 400, 200),  # overlaps the kernel by 100
        _op("fusion.1", 900, 100),
        _op("fusion.3", 1200, 50),  # after the window
    ]
    r = tr.reduce(ops, spans)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(600e-9)  # [100, 600) and [900, 1000)
    assert r.kernel_s == pytest.approx(300e-9)
    assert r.device_ops[0] == ["body.7 (relax kernel)", pytest.approx(300e-9)]
    assert dict(r.device_ops)["fusion.1"] == pytest.approx(200e-9)
    # idle [600, 900): its middle, 750, lies in bench.batch; [1000, 1100)
    # lies in bench.count
    assert r.idle_gaps == [["bench.batch", pytest.approx(300e-9)],
                           ["bench.count", pytest.approx(100e-9)]]


def test_busy_is_averaged_over_devices():
    spans = [tr.Span("bench.window", 0, 100)]
    ops = [_op("a", 0, 100, device="/device:TPU:0"),
           _op("a", 0, 50, device="/device:TPU:1")]
    r = tr.reduce(ops, spans)
    assert r.n_devices == 2 and r.busy_s == pytest.approx(75e-9)


def test_reduce_needs_exactly_one_window():
    with pytest.raises(ValueError):
        tr.reduce([], [])


def test_control_flow_is_not_an_op():
    assert tr.CONTAINER.search("%while.7 = (s32[], f32[8]) while(%t), body=%b")
    assert not tr.CONTAINER.search("%body.7 = f32[8] custom-call(%a), x")
    assert tr.short_name("%fusion.46 = s32[8,8]{0,1} fusion(%a)") == "fusion.46"


def test_reduce_on_the_recorded_chip_trace():
    ops, spans = tr.read_xplane(FIXTURE)
    assert len(ops) == 1956 and sum(op.is_kernel for op in ops) == 50
    r = tr.reduce(ops, spans)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.233241547, rel=1e-9)
    assert r.busy_s == pytest.approx(0.200599995, rel=1e-9)
    assert r.kernel_s == pytest.approx(0.0630619, rel=1e-9)
    assert r.device_ops[0] == ["fusion.53", pytest.approx(0.07798861, rel=1e-9)]
    assert [g[0] for g in r.idle_gaps[:2]] == ["bench.count", "bench.count"]
