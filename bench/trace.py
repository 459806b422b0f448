"""Reduction of a profiler trace to device busy time, kernel time and the
longest idle gaps.

``read_xplane`` takes the events the reduction needs out of the
``.xplane.pb`` that ``jax.profiler`` writes: every operation on a device
plane's ``XLA Ops`` line but control flow (a ``while`` op spans its whole
loop), and the benchmark's own host spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation``).  ``reduce`` then works on
plain tuples, so a small recorded trace checks it without a chip.

All intervals are clipped to the ``bench.window`` span.  Busy time is the
union of the operation intervals, averaged over the devices, and kept per
device besides; kernel time is the union of the relax kernel's intervals,
collective time the union of the collectives' (ops whose HLO opcode is
``all-to-all``, ``all-reduce``, ``all-gather``, ``reduce-scatter`` or
``collective-permute``, or one of their ``-start``/``-done`` halves; on the
TPU their names are JAX's, ``pmax.41`` or ``all_to_all.4``), both averaged
over the devices; an op's seconds in ``device_ops`` are averaged over the
devices too.  An idle
gap is named after the innermost ``bench.*`` span (other than the window)
that covers its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
#: control flow, whose intervals enclose the ops it runs: left out, so that
#: the gaps between the ops of a loop body count as idle
CONTAINER = re.compile(r"(?<![\w-])(while|conditional|call)\(")
#: what marks the relax kernel's events: the custom call Pallas lowers to on
#: the TPU.  The event carries the op's HLO text and not the kernel's name,
#: so this holds while the relax kernel is the window's only Pallas kernel
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
#: the opcode of a collective op in its HLO text, ``%pmax.41 = s32[8]{0}
#: all-reduce(...)``: a fusion that takes a collective's result names it
#: only as an operand
COLLECTIVE = re.compile(
    r" (all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(-start|-done)?\("
)


@dataclasses.dataclass(frozen=True)
class Op:
    device: str
    name: str
    start_ns: float
    dur_ns: float
    is_kernel: bool
    is_collective: bool = False


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass(frozen=True)
class Reduced:
    window_s: float
    busy_s: float  # averaged over devices
    kernel_s: float  # averaged over devices
    n_devices: int
    device_busy_s: list  # busy seconds of each device, by device name
    collective_s: float  # averaged over devices
    device_ops: list  # [[name, seconds]], most time first, at most 10
    idle_gaps: list  # [[host span name, seconds]], longest first, at most 10


def short_name(name: str) -> str:
    """``fusion.46`` of an op event named by its HLO text,
    ``%fusion.46 = s32[8,8]{...} fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> tuple[list[Op], list[Span]]:
    """Device ops and ``bench.*`` host spans of one ``.xplane.pb`` file."""
    import jax

    prof = jax.profiler.ProfileData.from_file(path)
    ops, spans = [], []
    for plane in prof.planes:
        on_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if on_device and CONTAINER.search(ev.name):
                    continue
                if on_device:
                    kernel = KERNEL_MARK in ev.name
                    name = short_name(ev.name) + (" (relax kernel)" if kernel else "")
                    ops.append(Op(plane.name, name, ev.start_ns, ev.duration_ns,
                                  kernel, bool(COLLECTIVE.search(ev.name))))
                elif ev.name.startswith("bench."):
                    spans.append(Span(ev.name, ev.start_ns, ev.duration_ns))
    return ops, spans


def find_xplane(log_dir: str) -> str | None:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _union(intervals) -> list[tuple[float, float]]:
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        elif end > start:
            out.append((start, end))
    return out


def _total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def reduce(ops: list[Op], spans: list[Span]) -> Reduced:
    """Busy, kernel and idle time of the devices inside ``bench.window``."""
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    w0 = windows[0].start_ns
    w1 = w0 + windows[0].dur_ns

    def clip(op):
        return max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1)

    by_device = defaultdict(list)
    for op in ops:
        start, end = clip(op)
        if end > start:
            by_device[op.device].append((op, start, end))
    n_dev = max(1, len(by_device))
    kernel = collective = 0.0
    device_busy = []
    per_name = defaultdict(float)
    gaps = []
    for _, device_ops in sorted(by_device.items()):
        busy_iv = _union((s, e) for _, s, e in device_ops)
        device_busy.append(_total(busy_iv))
        kernel += _total(_union((s, e) for op, s, e in device_ops if op.is_kernel))
        collective += _total(_union((s, e) for op, s, e in device_ops
                                    if op.is_collective))
        for op, s, e in device_ops:
            per_name[op.name] += e - s
        edges = [w0] + [x for iv in busy_iv for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    inner = [s for s in spans if s.name != WINDOW_SPAN]

    def label(gap):
        mid = (gap[0] + gap[1]) / 2
        covering = [s for s in inner if s.start_ns <= mid <= s.start_ns + s.dur_ns]
        return min(covering, key=lambda s: s.dur_ns).name if covering else "host"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    top_ops = sorted(per_name.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(device_busy) / n_dev * 1e-9,
        kernel_s=kernel / n_dev * 1e-9,
        n_devices=len(by_device),
        device_busy_s=[ns * 1e-9 for ns in device_busy],
        collective_s=collective / n_dev * 1e-9,
        device_ops=[[name, ns / n_dev * 1e-9] for name, ns in top_ops],
        idle_gaps=[[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:10]],
    )
