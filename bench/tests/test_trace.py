"""The trace reduction gives known numbers: on hand-made events, and on a
small trace recorded on a TPU v5e (two BFS batches of 8 keys on a scale-14
Kronecker graph, pallas backend)."""

from __future__ import annotations

import os

import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "bfs8_s14_v5e.xplane.pb")


def _op(name, start, dur, kernel=False, device="/device:TPU:0", collective=False):
    return tr.Op(device, name, start, dur, kernel, collective)


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


def test_collectives_and_each_devices_busy_time():
    spans = [tr.Span("bench.window", 0, 100)]
    ops = [_op("all_to_all.3", 0, 20, collective=True, device="/device:TPU:0"),
           _op("pmax.2", 10, 20, collective=True, device="/device:TPU:0"),  # overlaps
           _op("fusion.7", 30, 70, device="/device:TPU:0"),
           _op("all-reduce.5", 0, 10, collective=True, device="/device:TPU:1"),
           _op("psum.1", 40, 10, collective=True, device="/device:TPU:1"),
           _op("fusion.8", 60, 20, device="/device:TPU:1")]
    r = tr.reduce(ops, spans)
    assert r.device_busy_s == [pytest.approx(100e-9), pytest.approx(40e-9)]
    assert r.busy_s == pytest.approx(70e-9)
    # [0, 30) on device 0, [0, 10) and [40, 50) on device 1
    assert r.collective_s == pytest.approx(25e-9)
    assert dict(r.device_ops)["fusion.7"] == pytest.approx(35e-9)  # per device


#: op texts from a traced window of the 4-chip cell on four v5e chips: JAX
#: names the collectives (pmax, all_to_all, psum), the opcode says what they are
V5E_OPS = {
    "%pmax.38 = s32[]{:T(128)} all-reduce(s32[]{:T(128)S(6)} %convert_element_type.55),"
    " channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true,"
    " to_apply=%region_16.16": True,
    "%all_to_all.4 = f32[8,4,412203]{2,0,1:T(8,128)S(1)} all-to-all(f32[8,4,412203]"
    "{2,0,1:T(8,128)S(1)} %get-tuple-element.780), channel_id=1,"
    " replica_groups={{0,1,2,3}}, dimensions={1}": True,
    "%all-reduce.5 = (s32[8,512,8]{1,2,0:T(8,128)}, s32[8,512]{1,0:T(8,128)})"
    " all-reduce(s32[8,512,8]{1,2,0:T(8,128)S(1)} %copy.93, s32[8,512]"
    "{1,0:T(8,128)S(1)} %copy.96), channel_id=1": True,
    "%fusion.56 = s32[8]{0:T(128)S(1)} fusion(s32[8]{0:T(128)S(1)} %copy-done.27,"
    " s32[8]{0:T(128)S(1)} %pmax.41), kind=kLoop,"
    " calls=%fused_computation.16.clone.clone": False,
    "%compare_not_fusion = pred[8]{0:T(512)(128)(4,1)} fusion(s32[8]{0:T(128)S(1)}"
    " %pmax.35), kind=kLoop, calls=%fused_computation.36": False,
    "%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %p)": True,
    "%collective-permute-done.1 = f32[8]{0} collective-permute-done(%cp)": True,
}


@pytest.mark.parametrize("text", sorted(V5E_OPS))
def test_collectives_are_told_by_their_opcode(text):
    assert bool(tr.COLLECTIVE.search(text)) is V5E_OPS[text]


def test_the_recorded_one_chip_trace_has_no_collective():
    ops, _ = tr.read_xplane(FIXTURE)
    assert not any(op.is_collective for op in ops)


def _ctx(reduced, relax_bytes=0):
    from bench import harness

    return harness.Context(n=1, n_edges=1, batches=[], window_s=1.0, setup_s=1.0,
                           setup_seconds={}, relax_bytes=relax_bytes,
                           trace=reduced, peaks={"hbm_bytes_per_s": 1e9})


def _reduced(**kw):
    base = dict(window_s=4.0, busy_s=1.5, kernel_s=1.0, n_devices=2,
                device_busy_s=[2.0, 1.0], collective_s=0.5, device_ops=[],
                idle_gaps=[])
    return tr.Reduced(**{**base, **kw})


def test_device_imbalance_reads_the_busiest_device_over_the_mean():
    from bench.harness import load_reader

    value = load_reader("device_imbalance.teps")(_ctx(_reduced()))
    assert value == pytest.approx(100.0 / 3.0)  # 2 s over a mean of 1.5 s


def test_collective_share_reads_collective_time_over_the_window():
    from bench.harness import load_reader

    spans = [tr.Span("bench.window", 0, 200)]
    ops = [_op("all_to_all.1", 0, 50, collective=True, device="/device:TPU:0"),
           _op("pmax.3", 50, 30, collective=True, device="/device:TPU:0"),
           _op("psum.3", 0, 20, collective=True, device="/device:TPU:1"),
           _op("fusion.2", 20, 100, device="/device:TPU:1")]
    read = load_reader("collective_share.teps")
    assert read(_ctx(tr.reduce(ops, spans))) == pytest.approx(25.0)  # 50 of 200
    assert read(_ctx(tr.reduce([_op("fusion.2", 0, 10)], spans))) is None


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_relax_roofline_divides_by_the_devices_peak(n_devices):
    from bench.harness import load_reader

    read = load_reader("relax_roofline.teps")
    one = read(_ctx(_reduced(n_devices=1, kernel_s=2.0), relax_bytes=10**9))
    assert one == 50.0  # 1 s at the one device's peak, over 2 s of kernel
    value = read(_ctx(_reduced(n_devices=n_devices, kernel_s=2.0), relax_bytes=10**9))
    assert value == pytest.approx(50.0 / n_devices)
