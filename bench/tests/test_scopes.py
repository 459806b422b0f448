"""The scope split (``bench/scopes.py``): on hand-made events, on a small
HLO text, on one traced window of a small cell on the CPU, and on a trace
recorded on a TPU v5e with the device scopes in place: two BFS batches of
8 keys on a scale-14 Kronecker graph, pallas backend, made by
``scopes.traced_run`` on ``graph500-s20.bfs8`` cut to scale 14 and 16
keys, with the window's HLO text beside it.  The checkout's path in both
files reads ``<checkout>``."""

from __future__ import annotations

import os

import pytest

from bench import scopes
from bench import trace as tr
from bench.tests.conftest import small_cell

TPU0 = "/device:TPU:0"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
SCOPED_TRACE = os.path.join(FIXTURES, "bfs8_s14_v5e_scoped.xplane.pb")
SCOPED_HLO = os.path.join(FIXTURES, "bfs8_s14_v5e_scoped.hlo.txt")

HLO = '''HloModule jit__window_impl, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %gather.4 = f32[8]{0} gather(f32[8]{0} %p), metadata={op_name="jit(_window_impl)/while/body/while/body/local/gather/gather" source_file="t.py" source_line=1}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.46 = s32[8,8]{1,0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_window_impl)/while/body/local/counters/vmap()/scatter-add" source_file="t.py" source_line=3}
  %copy.40 = f32[8]{0} copy(f32[8]{0} %a)
  %body.7 = f32[8,16]{1,0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_window_impl)/while/body/remote/relax/pallas_call"}
  %gather.9 = f32[8]{0} gather(f32[8]{0} %a), metadata={op_name="jit(_window_impl)/while/body/gather"}
  %all-to-all.2 = f32[8]{0} all-to-all(f32[8]{0} %a), metadata={op_name="jit(_window_impl)/while/body/local/frontier/collective/pmax"}
  ROOT %compare.3 = pred[8]{0} compare(f32[8]{0} %a, f32[8]{0} %a), direction=GT, metadata={op_name="jit(_window_impl)/finish/vmap()/gt"}
}
'''


def test_scope_map_reads_op_name_metadata():
    module, smap = scopes.scope_map(HLO)
    assert module == "jit__window_impl"
    assert smap == {
        "gather.4": "local/gather",
        "fusion.46": "local/counters",
        "body.7": "remote/relax",
        "all-to-all.2": "local/collective",
        "compare.3": "finish",
    }  # copy.40 has no metadata; gather.9 has no scope, only its primitive


def _op(name, start, dur, kernel=False):
    return tr.Op(TPU0, name, start, dur, kernel)


def test_reduce_on_hand_made_events():
    spans = [
        tr.Span("bench.window", 100, 1000),
        tr.Span("bench.batch", 100, 700),
        tr.Span("graph.launch", 100, 20),
        tr.Span("graph.fetch", 120, 680),
        tr.Span("bench.count", 800, 300),
    ]
    ops = [
        _op("fusion.1", 100, 100),
        _op("body.7 (relax kernel)", 200, 200, kernel=True),
        _op("fusion.2", 350, 100),  # overlaps the kernel by 50
        _op("copy.3", 450, 50),  # in the module, not in the map
        _op("fusion.1", 600, 100),  # in the map, outside the module
    ]
    modules = {TPU0: [(100, 550)]}
    smap = {"fusion.1": "local/gather", "body.7": "local/relax",
            "fusion.2": "local/counters"}
    s = scopes.reduce(ops, spans, modules, smap)
    assert s.path_s == pytest.approx({
        "local/gather": 100e-9, "local/relax": 200e-9,
        "local/counters": 50e-9, "unscoped": 150e-9})
    assert s.role_s == pytest.approx(
        {"gather": 100e-9, "relax": 200e-9, "counters": 50e-9})
    assert s.unscoped_s == pytest.approx(150e-9)
    assert sum(s.path_s.values()) == pytest.approx(s.reduced.busy_s, abs=1e-18)
    assert s.share("gather") == pytest.approx(10.0)
    assert s.share("frontier") == 0.0
    ops_s = dict(s.reduced.device_ops)
    assert ops_s["fusion.1 [local/gather]"] == pytest.approx(100e-9)
    assert ops_s["fusion.1 [unscoped]"] == pytest.approx(100e-9)
    assert ops_s["body.7 (relax kernel) [local/relax]"] == pytest.approx(200e-9)
    # idle [500, 600): inside bench.batch, graph.fetch the innermost span;
    # [700, 1100): its middle, 900, lies in bench.count alone
    assert s.reduced.idle_gaps == [["bench.count", pytest.approx(400e-9)],
                                   ["graph.fetch", pytest.approx(100e-9)]]


def test_summary_shares_add_up():
    spans = [tr.Span("bench.window", 0, 1000)]
    ops = [_op("a", 0, 300), _op("b", 300, 200), _op("c", 500, 100)]
    s = scopes.reduce(ops, spans, {TPU0: [(0, 540)]},
                      {"a": "local/gather", "b": "remote/counters", "c": "finish"})
    out = scopes.summary(s)
    assert out["gather_share"] == pytest.approx(30.0)
    assert out["counters_share"] == pytest.approx(20.0)
    assert out["scope_s"] == pytest.approx({"gather": 300e-9, "counters": 200e-9})
    assert out["unscoped_s"] == pytest.approx(100e-9)  # c: its middle lies past the module
    assert sum(out["shares"].values()) + out["device_idle"] == pytest.approx(100.0)


def test_traced_run_on_a_small_cell(tmp_path):
    """One traced window on the CPU: the set-up's program spans come back,
    and the window's HLO text maps every plane and role.  The CPU trace has
    no device plane, so no device time is put down to any scope."""
    out = scopes.traced_run(small_cell("graph500-s20.bfs8"), 2**31 + 11, 0.0,
                            str(tmp_path))
    for name in ("graph.partition.seeds", "graph.partition.grow",
                 "graph.partition.refine", "graph.layout.local",
                 "graph.layout.remote", "graph.engine_build", "graph.init",
                 "graph.launch", "graph.fetch"):
        assert out["setup_spans"][name] > 0, name
    assert out["batches"] == 2 and out["busy_s"] == 0.0
    assert out["gather_share"] == 0.0 and out["counters_share"] == 0.0
    with open(os.path.join(tmp_path, scopes.WINDOW_HLO)) as f:
        module, smap = scopes.scope_map(f.read())
    assert module == "jit__window_impl"
    assert {f"{p}/{r}" for p in ("local", "remote")
            for r in ("gather", "relax", "counters", "frontier")} | {"finish"} \
        == set(smap.values())


@pytest.fixture(scope="module")
def recorded():
    with open(SCOPED_HLO) as f:
        module, smap = scopes.scope_map(f.read())
    ops, spans, modules = scopes.read(SCOPED_TRACE, module)
    return scopes.reduce(ops, spans, modules, smap), ops, spans, modules


def test_recorded_trace_roles_add_up_to_busy(recorded):
    s, ops, _, modules = recorded
    r = s.reduced
    assert len(ops) == 1672 and sum(op.is_kernel for op in ops) == 48
    assert list(modules) == [TPU0] and len(modules[TPU0]) == 2  # two batches
    assert r.window_s == pytest.approx(0.16167177, rel=1e-9)
    assert r.busy_s == pytest.approx(0.149718527, rel=1e-9)
    assert sum(s.role_s.values()) + s.unscoped_s == pytest.approx(r.busy_s, abs=1e-9)
    assert s.role_s == pytest.approx({
        "gather": 0.029952158, "relax": 0.049157482, "counters": 0.069896828,
        "frontier": 0.000100236, "finish": 0.000237944}, rel=1e-6)
    assert s.unscoped_s / r.window_s < 0.01


def test_recorded_trace_relax_role_is_the_kernel(recorded):
    """The relax role is the kernel plus the pads around it: within half a
    point of the window of the kernel's own time."""
    s, *_ = recorded
    r = s.reduced
    assert s.role_s["relax"] >= r.kernel_s
    assert 100 * (s.role_s["relax"] - r.kernel_s) / r.window_s < 0.5
    ops_s = dict(r.device_ops)
    assert ops_s["fusion.53 [local/counters]"] == pytest.approx(0.044250939, rel=1e-6)
    assert ops_s["relax.7 (relax kernel) [local/relax]"] == pytest.approx(
        0.03183192, rel=1e-6)


def test_recorded_trace_labels_gaps_with_program_spans(recorded):
    """Inside a batch, the device waits on the host's state upload
    (``graph.init``) and result transfer (``graph.fetch``)."""
    s, _, spans, _ = recorded
    assert {sp.name for sp in spans} >= {"graph.init", "graph.launch", "graph.fetch"}
    labels = [name for name, _ in s.reduced.idle_gaps]
    assert labels[:5] == ["graph.fetch", "graph.fetch", "graph.init",
                          "graph.init", "graph.init"]
    assert all(name.startswith("graph.") for name in labels)
