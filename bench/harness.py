"""The benchmark harness: one run of one cell, driven by data.

``BENCHMARK.json`` names the cells.  A cell ``<config>.<traffic>`` finds
its files by name:

* ``bench/configs/<config>.json``: the graph (generator, sizes, seed), its
  partitioning and the engine configuration;
* ``bench/generators/<generator>.py``: the graph generator the
  configuration names (``bench/graphs.py``);
* ``bench/partitioners/<method>.py``: the program's partitioner the
  configuration's ``partition.method`` names, as ``partition(graph, spec)``;
* ``bench/traffic/<traffic>.json``: the program kind, the keys per batch
  and where they are drawn from;
* ``bench/programs/<program>.py``: the program kind the traffic names: its
  ``make(traffic)``, its comparison with the plain reference
  (``compare``), its control (``control``), and optionally its own
  ``run_batch`` and ``warm_up`` where it drives the system otherwise than
  by one ``GraphSession.run`` per batch;
* ``bench/limits/<cell>.json``: the limit of each number ``compare``
  computes;
* ``bench/metrics/<metric>.py``: one reader per metric, a ``read(ctx)``
  that returns the value or None where it finds nothing.  A metric named
  ``<name>.<suffix>`` (split by the end-to-end metric it moves) falls back
  to ``<name>.py``.

A run generates the graph from the configuration's seed, partitions it,
opens a ``GraphSession`` (over a mesh of the cell's chips where it has more
than one) and compiles the cell's one window program, then
runs whole passes over the cell's fixed pool of key batches in a closed
loop: a pass starts only while less than ``seconds`` have passed, and the
window ends when the last batch's result is on the host.  ``--seed``
orders the batches and the keys in each, so every seed does the same
work.  Once the window has closed, every answer of every batch is compared
with the plain reference (``bench/reference.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import ROOT, load_module

# -- compile events (JAX's own) ----------------------------------------------

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """JAX's trace, lowering and compile events from ``listen()`` on.  JAX's
    listeners are process-wide and cannot be removed, so one log is made
    per process and shared."""

    _instance = None

    def __init__(self):
        self.events = []  # (end perf_counter, seconds, name)

    @classmethod
    def listen(cls) -> "CompileLog":
        if cls._instance is None:
            import jax

            log = cls()

            def on_event(name, secs, **_):
                if name in _COMPILE_EVENTS:
                    log.events.append((time.perf_counter(), secs, name))

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._instance = log
        return cls._instance

    def seconds_since(self, t0: float) -> float:
        """Seconds compiling since ``t0``: the union of the events that ended
        after it (tracing nests, so the events overlap)."""
        spans = sorted((end - secs, end) for end, secs, _ in self.events if end > t0)
        total, reach = 0.0, t0
        for start, end in spans:
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total

    def compiles_since(self, t0: float) -> int:
        return sum(1 for end, _, name in self.events
                   if end > t0 and name == _BACKEND_COMPILE)


# -- the cell's files ----------------------------------------------------------


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    kind: object  # the module bench/programs/<traffic program>.py
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    spec = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    traffic = load_json(root, "bench", "traffic", w["traffic"] + ".json")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(root, "bench", "configs", w["config"] + ".json"),
        traffic=traffic,
        limits=load_json(root, "bench", "limits", workload + ".json"),
        kind=load_module("programs", traffic["program"], root),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, workload)],
    )


def load_reader(name: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``, or of
    ``<base>.py`` for a metric ``<base>.<suffix>`` that has no file of its
    own."""
    folder = os.path.join(root, "bench", "metrics")
    if not os.path.isfile(os.path.join(folder, name + ".py")) and "." in name:
        name = name.split(".", 1)[0]
    return load_module("metrics", name, root).read


# -- devices -----------------------------------------------------------------


def accelerators(chips: int):
    """The accelerators this cell runs on, or exit non-zero: a run that
    finds no chip, or fewer than the cell asks for, fails."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit("bench: needs an accelerator; JAX found only the CPU")
    if len(devices) < chips:
        raise SystemExit(f"bench: needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peak_bytes_in_use(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def device_peaks(kind: str, root: str = ROOT) -> dict:
    """The peak table's entry for ``kind``; an unknown device is an error."""
    table = load_json(root, "bench", "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: {kind!r} is not in bench/peaks.json")
    return table[kind]


# -- set-up --------------------------------------------------------------------


@dataclasses.dataclass
class Setup:
    n: int
    src: np.ndarray  # [E] int32 arcs, sorted by (src, dst)
    dst: np.ndarray
    weights: np.ndarray | None
    degree: np.ndarray  # [n] arcs per vertex
    session: object  # GraphSession
    program: object
    run_batch: object  # (setup, keys) -> result with dist, n_supersteps, inner_iters
    local_arcs: int
    remote_arcs: int
    seconds: dict  # generate_s, partition_s, layout_s, session_s, warmup_s


def session_run(setup: Setup, keys):
    """One batch as most cells drive it: one ``GraphSession.run``."""
    return setup.session.run(setup.program, keys)


def empty_frontier_warm_up(setup: Setup, s_batch: int) -> None:
    """Compile the batch's window program and run it once on a batch whose
    frontier is empty, so it converges at once."""
    import jax.numpy as jnp

    session, program = setup.session, setup.program
    state = session.init_state(np.arange(s_batch), program=program)
    state = state._replace(frontier=jnp.zeros_like(state.frontier))
    session.run_window(state, session.engine(program).m_max, program=program)


def set_up(cell: Cell, root: str = ROOT, devices=None) -> Setup:
    """Graph, partition, layout, session, and the window program compiled
    and warmed up on the cell's batch shape.  A cell on more than one chip
    opens its session over a mesh of ``devices`` (JAX's first ``chips``
    where not given), the parts in contiguous blocks per device, and builds
    the mesh layout that its engine then finds in the layout cache."""
    from bench.graphs import make_arcs
    from repro.dist.sharding import partition_mesh
    from repro.graph.config import EngineConfig
    from repro.graph.partition import (
        contiguous_device_map,
        mesh_edge_layout,
        partitioned_edge_layout,
    )
    from repro.graph.session import open_session
    from repro.graph.structs import Graph

    cfg = cell.config
    secs = {}
    t = time.perf_counter()
    n, src, dst, weights = make_arcs(cfg, root)
    secs["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    part = cfg["partition"]
    partitioner = load_module("partitioners", part["method"], root)
    pg = partitioner.partition(Graph(n, src, dst, weights), part)
    secs["partition_s"] = time.perf_counter() - t
    engine = EngineConfig(**cfg["engine"])
    if cell.chips > 1:
        engine = engine.replace(mesh=partition_mesh(cell.chips, devices))
    t = time.perf_counter()
    if engine.mesh is not None:
        layout = mesh_edge_layout(pg, contiguous_device_map(pg.n_parts, cell.chips),
                                  cell.chips, mirror_degree=engine.mirror_degree)
        local_arcs = int(layout.lvalid.sum())
        remote_arcs = int(layout.rvalid.sum() + layout.mvalid.sum())
    else:
        layout = partitioned_edge_layout(pg)
        local_arcs, remote_arcs = layout.local.n_edges, layout.remote.n_edges
    secs["layout_s"] = time.perf_counter() - t
    t = time.perf_counter()
    session = open_session(pg, engine)
    program = cell.kind.make(cell.traffic)
    session.engine(program)
    secs["session_s"] = time.perf_counter() - t
    setup = Setup(
        n=n, src=src, dst=dst, weights=weights,
        degree=np.bincount(src, minlength=n), session=session, program=program,
        run_batch=getattr(cell.kind, "run_batch", session_run),
        local_arcs=local_arcs, remote_arcs=remote_arcs,
        seconds=secs,
    )
    t = time.perf_counter()
    warm_up = getattr(cell.kind, "warm_up", empty_frontier_warm_up)
    warm_up(setup, int(cell.traffic["keys_per_batch"]))
    secs["warmup_s"] = time.perf_counter() - t
    return setup


def key_pool(cell: Cell, degree: np.ndarray) -> np.ndarray:
    """``[B, S]``: the cell's batches of keys, the same in every run.  For
    ``pool: degree_ge_1``, ``keys`` distinct vertices of degree >= 1 drawn
    with the configuration's graph seed, as Graph500 draws its search keys
    once per graph; for ``pool: none`` (a program with no keys), one batch
    of a placeholder key."""
    traffic = cell.traffic
    s_batch = int(traffic["keys_per_batch"])
    if traffic["pool"] == "none":
        return np.zeros((1, s_batch), np.int64)
    if traffic["pool"] != "degree_ge_1":
        raise ValueError(f"unknown key pool {traffic['pool']!r}")
    candidates = np.flatnonzero(degree > 0)
    k = min(int(traffic["keys"]), candidates.size) // s_batch * s_batch
    rng = np.random.default_rng(int(cell.config["graph"]["seed"]))
    return rng.choice(candidates, size=k, replace=False).reshape(-1, s_batch)


# -- the window ----------------------------------------------------------------


@dataclasses.dataclass
class Batch:
    keys: np.ndarray
    dist: np.ndarray  # [S, n] the program's state
    n_supersteps: np.ndarray
    inner_iters: np.ndarray
    edges: np.ndarray  # [S] undirected edges in each key's reached set
    seconds: float


def reached_edges(degree: np.ndarray, dist: np.ndarray) -> np.ndarray:
    """[S] undirected edges among the vertices each row reached (finite
    state): the key's component, where the answer is right."""
    return np.array([degree[row].sum() // 2 for row in np.isfinite(dist)])


def run_window(setup: Setup, pool: np.ndarray, seed: int, seconds: float,
               annotate) -> tuple[list, float]:
    """Whole passes over the key pool in a closed loop, each pass in an
    order drawn by ``seed`` (of the batches, and of the keys in each): a
    pass starts only while less than ``seconds`` have passed, and the
    window ends when its last batch's result is on the host.  So every
    seed does the same work, in another order.  (batches, window s)."""
    rng = np.random.default_rng(seed)
    done = []
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while not done or time.perf_counter() - t0 < seconds:
            for i in rng.permutation(len(pool)):
                keys = rng.permutation(pool[i])
                t = time.perf_counter()
                with annotate("bench.batch"):
                    res = setup.run_batch(setup, keys)
                with annotate("bench.count"):
                    edges = reached_edges(setup.degree, res.dist)
                done.append(Batch(keys, res.dist, res.n_supersteps,
                                  res.inner_iters, edges, time.perf_counter() - t))
    return done, time.perf_counter() - t0


# -- the comparison with the reference -----------------------------------------


def stacked(batches: list) -> tuple[np.ndarray, np.ndarray]:
    """(keys [K], answers [K, n]) of every row of the window's batches."""
    return (np.concatenate([b.keys for b in batches]),
            np.concatenate([b.dist for b in batches]))


def edge_count_bad(setup: Setup, batches: list) -> np.ndarray:
    """[K] whether each key's edge count, which ``teps`` sums, differs from
    the edges of its component in the reference."""
    from bench import reference as ref

    keys, _ = stacked(batches)
    edges = np.concatenate([b.edges for b in batches])
    return edges != ref.component_edges(setup.n, setup.src, setup.dst)[keys]


def compare(cell: Cell, setup: Setup, batches: list) -> tuple[dict, int]:
    """({number: value}, failed answers) of every answer in the window, by
    the cell's program kind."""
    return cell.kind.compare(cell, setup, batches)


# -- metrics -------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a metric's reader may read."""

    n: int  # vertices
    n_edges: int  # undirected edges
    batches: list
    window_s: float  # host clock, first batch to the last result
    setup_s: float
    setup_seconds: dict  # start_s, generate_s, partition_s, ..., compile_s
    relax_bytes: int  # least bytes of the window's relax passes
    trace: object = None  # bench.trace.Reduced, where traced and read
    peaks: dict = dataclasses.field(default_factory=dict)  # bench/peaks.json entry


def relax_bytes(cell: Cell, setup: Setup, batches: list) -> int:
    from bench.roofline import run_bytes

    return sum(
        run_bytes(setup.local_arcs, setup.remote_arcs, setup.n, b.keys.size,
                  cell.kind.READS_WEIGHT, b.n_supersteps, b.inner_iters,
                  setup.program.stationary)
        for b in batches
    )


def read_metrics(metrics: list, ctx: Context, root: str) -> dict:
    out = {}
    for m in metrics:
        value = load_reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- one run -------------------------------------------------------------------


def no_annotation(_name):
    return contextlib.nullcontext()


def run(workload: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, root: str = ROOT, cell: Cell | None = None,
        check_device: bool = True) -> dict:
    """One run of one cell of ``root``'s ``BENCHMARK.json``; returns the
    result line's object and the run's details.  Tests pass a small
    ``cell`` and ``check_device=False``."""
    import jax

    from bench import trace as tr
    from bench.roofline import passes

    cell = cell or load_cell(workload, root)
    devices = accelerators(cell.chips) if check_device else jax.devices()[:cell.chips]
    log = CompileLog.listen()
    t_setup = time.perf_counter()
    setup = set_up(cell, root, devices)
    setup.seconds["start_s"] = t_setup - t_start
    pool = key_pool(cell, setup.degree)
    setup_s = time.perf_counter() - t_start
    setup.seconds["compile_s"] = log.seconds_since(t_start)

    annotate = jax.profiler.TraceAnnotation if trace else no_annotation
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    t_window = time.perf_counter()
    if trace:
        jax.profiler.start_trace(log_dir)
    try:
        done, window_s = run_window(setup, pool, seed, seconds, annotate)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = log.compiles_since(t_window)
    memory_peak = peak_bytes_in_use(devices)
    setup.session = None  # the engine's device arrays go with the session

    reduced = None
    if trace:
        path = tr.find_xplane(log_dir)
        reduced = tr.reduce(*tr.read_xplane(path)) if path else None
        shutil.rmtree(log_dir, ignore_errors=True)

    t_check = time.perf_counter()
    values, failed = compare(cell, setup, done)
    check_s = time.perf_counter() - t_check
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in values.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    ctx = Context(n=setup.n, n_edges=setup.src.size // 2, batches=done,
                  window_s=window_s, setup_s=setup_s, setup_seconds=setup.seconds,
                  relax_bytes=relax_bytes(cell, setup, done), trace=reduced)
    info = {"setup": setup.seconds, "setup_s": setup_s, "window_s": window_s,
            "batches": len(done), "batch_s": [b.seconds for b in done],
            "compiles_in_window": in_window, "check_s": check_s,
            "supersteps": [int(np.max(b.n_supersteps)) for b in done],
            "local_passes": [passes(b.n_supersteps, b.inner_iters,
                                    setup.program.stationary)[0] for b in done],
            "relax_bytes": ctx.relax_bytes}
    if trace:
        if check_device:
            ctx.peaks = device_peaks(d0.device_kind, root)
        metrics = read_metrics(cell.per_layer, ctx, root)
        device["busy_s"] = reduced.busy_s if reduced else 0.0
        device["window_s"] = reduced.window_s if reduced else window_s
        if reduced:
            info["kernel_s"] = reduced.kernel_s
    else:
        metrics = read_metrics(cell.end_to_end, ctx, root)
    result = {"correct": correct, "attempted": int(sum(b.keys.size for b in done)),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = checks
    return {"result": result, "info": info}


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    print(json.dumps({"info": out["info"]}), flush=True)
    checks = out["result"]["checks"]
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    return 0
