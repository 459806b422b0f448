"""Device time by scope: each op of the window program put down to the
plane and role of the device scope it runs under (``repro.spans``), and
each idle gap to the innermost ``bench.*`` or ``graph.*`` host span.

An extension of ``bench/trace.py``'s reduction, over the same events:

* ``scope_map`` reads ``{instruction: "plane/role"}`` from the ``op_name``
  metadata of the compiled window's HLO text
  (``TraversalEngine.window_hlo``).  A profiler op event carries its
  instruction's text but not its metadata, and ``jax.profiler.ProfileData``
  exposes no event metadata, so each op is joined to the map by its short
  name (``fusion.46``).  A fusion carries its root's ``op_name``.
* ``read`` adds to ``trace.read_xplane``'s ops and ``bench.*`` spans the
  program's ``graph.*`` host spans and the device intervals of the window's
  HLO module: only ops inside those intervals are joined, all others are
  ``unscoped``.
* ``reduce`` is ``trace.reduce`` over ops named ``fusion.46 [local/counters]``
  with the ``graph.*`` spans among the labels, plus the seconds under each
  scope.  Time is put down to one scope at a time (where ops overlap, to the
  one that started first), so the scopes' seconds add up to ``busy_s``.

Run as a script, it runs one traced window of a cell and prints the split
as one JSON line, with the set-up's ``graph.*`` spans:

    python3 bench/scopes.py --workload graph500-s20.bfs8 --seed 7 \\
        --seconds 30 [--out DIR]

``--out`` keeps the trace and the window's HLO text (``window.hlo.txt``) in
``DIR``.  The program's HLO is that of the window ``run`` launches (depth
``m_max``, the cell's batch).
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict

if __package__ in (None, ""):  # run as a script: the checkout's bench and src
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

from bench import ROOT
from bench import trace as tr

PROGRAM_SPAN_PREFIX = "graph."
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
WINDOW_HLO = "window.hlo.txt"
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
    re.M,
)


def scope_map(hlo_text: str) -> tuple[str, dict]:
    """(module name, ``{instruction: "plane/role"}``) of a compiled HLO
    text.  The last part of an ``op_name`` is the primitive, not a scope."""
    from repro.spans import FINISH, scope_of

    module = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M).group(1)
    out = {}
    for name, op_name in _INSTRUCTION.findall(hlo_text):
        found = scope_of(op_name.split("/")[:-1])
        if found:
            out[name] = FINISH if found == (FINISH, FINISH) else "/".join(found)
    return module, out


def read(path: str, module: str) -> tuple[list, list, dict]:
    """(ops, spans, ``{device: [(start, end)]}``): ``trace.read_xplane``'s
    ops and ``bench.*`` spans, with the ``graph.*`` spans added, and the
    intervals in which each device ran ``module``."""
    import jax

    ops, spans = tr.read_xplane(path)
    intervals = defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(tr.DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_device and line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if on_device and ev.name.split("(", 1)[0] == module:
                    intervals[plane.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
                elif not on_device and ev.name.startswith(PROGRAM_SPAN_PREFIX):
                    spans.append(tr.Span(ev.name, ev.start_ns, ev.duration_ns))
    return ops, spans, {d: sorted(iv) for d, iv in intervals.items()}


@dataclasses.dataclass(frozen=True)
class Scoped:
    reduced: tr.Reduced  # op names suffixed by scope, gaps by graph.* spans
    path_s: dict  # {"local/gather": s, ..., "unscoped": s}, averaged over devices
    role_s: dict  # {"gather": s, ...}: path_s summed over planes

    @property
    def unscoped_s(self) -> float:
        return self.path_s.get(UNSCOPED, 0.0)

    def share(self, role: str) -> float:
        """Percent of the traced window spent under ``role``."""
        return 100.0 * self.role_s.get(role, 0.0) / self.reduced.window_s


def _scope_of_op(op, modules: dict, smap: dict) -> str:
    """The op's scope: joined by short name, where its middle lies inside
    one of its device's intervals of the window module."""
    iv = modules.get(op.device, ())
    mid = op.start_ns + op.dur_ns / 2
    i = bisect.bisect_right(iv, (mid, float("inf"))) - 1
    if i < 0 or mid > iv[i][1]:
        return UNSCOPED
    return smap.get(op.name.split(" ", 1)[0], UNSCOPED)


def reduce(ops: list, spans: list, modules: dict, smap: dict) -> Scoped:
    paths = [_scope_of_op(op, modules, smap) for op in ops]
    named = [dataclasses.replace(op, name=f"{op.name} [{p}]")
             for op, p in zip(ops, paths)]
    base = tr.reduce(named, spans)
    window = next(s for s in spans if s.name == tr.WINDOW_SPAN)
    w0, w1 = window.start_ns, window.start_ns + window.dur_ns
    by_device = defaultdict(list)
    for op, p in zip(ops, paths):
        start, end = max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1)
        if end > start:
            by_device[op.device].append((start, end, p))
    path_ns = defaultdict(float)
    for items in by_device.values():
        reach = float("-inf")
        for start, end, p in sorted(items):
            start = max(start, reach)
            if end > start:
                path_ns[p] += end - start
                reach = end
    n_dev = max(1, len(by_device))
    path_s = {p: ns / n_dev * 1e-9 for p, ns in sorted(path_ns.items())}
    role_s = defaultdict(float)
    for p, s in path_s.items():
        if p != UNSCOPED:
            role_s[p.rsplit("/", 1)[-1]] += s
    return Scoped(base, path_s, dict(role_s))


def summary(scoped: Scoped) -> dict:
    """The split as the result line would carry it: seconds, and shares of
    the traced window in percent."""
    r = scoped.reduced
    idle = 100.0 * (1.0 - r.busy_s / r.window_s)
    return {
        "window_s": r.window_s, "busy_s": r.busy_s, "kernel_s": r.kernel_s,
        "device_idle": idle, "relax_share": 100.0 * r.kernel_s / r.window_s,
        "gather_share": scoped.share("gather"),
        "counters_share": scoped.share("counters"),
        "scope_s": scoped.role_s, "path_s": scoped.path_s,
        "unscoped_s": scoped.unscoped_s,
        "shares": {k: 100.0 * v / r.window_s for k, v in scoped.path_s.items()},
        "breakdown": {"device_ops": r.device_ops, "idle_gaps": r.idle_gaps},
    }


def traced_run(cell, seed: int, seconds: float, log_dir: str, *,
               root: str = ROOT) -> dict:
    """Set-up and one traced window of ``cell``, as ``bench.harness.run``
    makes them; the trace and the window's HLO text stay in ``log_dir``.
    Returns the split (``summary``) with the set-up's spans."""
    import jax

    from bench import harness
    from repro import spans as sp

    with sp.collect() as records:
        setup = harness.set_up(cell, root)
    pool = harness.key_pool(cell, setup.degree)
    jax.profiler.start_trace(log_dir)
    try:
        done, window_s = harness.run_window(
            setup, pool, seed, seconds, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    engine = setup.session.engine(setup.program)
    hlo = engine.window_hlo(pool.shape[1], engine.m_max)
    with open(os.path.join(log_dir, WINDOW_HLO), "w") as f:
        f.write(hlo)
    module, smap = scope_map(hlo)
    path = tr.find_xplane(log_dir)
    out = summary(reduce(*read(path, module), smap))
    out.update(batches=len(done), host_window_s=window_s,
               setup_spans=sp.span_seconds(records))
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Split one traced window of a "
                                 "cell by device scope.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="keep the trace and the HLO text here")
    args = ap.parse_args(argv)

    from bench import harness
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    harness.accelerators(cell.chips)
    log_dir = args.out or tempfile.mkdtemp(prefix="bench-scopes-")
    os.makedirs(log_dir, exist_ok=True)
    t = time.perf_counter()
    try:
        out = traced_run(cell, args.seed, args.seconds, log_dir)
    finally:
        if not args.out:
            shutil.rmtree(log_dir, ignore_errors=True)
    out.update(workload=args.workload, seed=args.seed,
               run_s=time.perf_counter() - t)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
