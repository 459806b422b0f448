"""New cells are added as new files and new entries of BENCHMARK.json only,
and run with the harness as it is: no existing file is edited.

The first test adds a configuration, a traffic mix, limits and a per-layer
metric over the existing generator and program kind.  The second adds a
graph generator and a program kind of their own (WCC, driven one
superstep per window, as an elastic executor drives a traversal), with
their own reference and warm-up.  The third adds a partition method, and an
unknown one is refused."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import run_small

NEW_CONFIG = {
    "name": "kron-s8",
    "source": "https://graph500.org/?page_id=12",
    "graph": {"generator": "kronecker", "scale": 8, "edge_factor": 8,
              "a": 0.57, "b": 0.19, "c": 0.19, "seed": 3},
    "partition": {"method": "bfs_grow", "n_parts": 4, "seed": 2},
    "engine": {"backend": "pallas-interpret"},
    "reduced": [],
}
NEW_TRAFFIC = {"program": "bfs", "keys": 6, "keys_per_batch": 2,
               "pool": "degree_ge_1"}
NEW_READER = '''"""Mean supersteps of the window's batches."""


def read(ctx):
    return sum(int(b.n_supersteps.max()) for b in ctx.batches) / len(ctx.batches)
'''

ROUND_ROBIN_PARTITIONER = '''"""Vertex ``v`` in part ``v mod n_parts``."""

import numpy as np


def partition(graph, spec):
    from repro.graph.structs import PartitionedGraph

    k = int(spec["n_parts"])
    return PartitionedGraph(graph, k, (np.arange(graph.n_vertices) % k).astype(np.int32))
'''

RINGS_GENERATOR = '''"""Disjoint rings of equal size: ``n`` vertices, ``rings`` components."""

import numpy as np


def arcs(graph):
    n, size = int(graph["n"]), int(graph["n"]) // int(graph["rings"])
    v = np.arange(n)
    nxt = v // size * size + (v % size + 1) % size
    src, dst = np.concatenate([v, nxt]), np.concatenate([nxt, v])
    order = np.lexsort((dst, src))
    return n, src[order].astype(np.int32), dst[order].astype(np.int32)
'''

WCC_PROGRAM = '''"""WCC by min labels, one superstep per window; compared with scipy's
components (``label_mismatch``, vertices)."""

import types

import numpy as np

READS_WEIGHT = False


def make(traffic):
    from repro.graph.program import WccProgram

    return WccProgram()


def warm_up(setup, s_batch):
    state = setup.session.init_state(np.arange(s_batch), program=setup.program)
    setup.session.run_window(state, 1, program=setup.program)


def run_batch(setup, keys):
    state = setup.session.init_state(keys, program=setup.program)
    inner = []
    while True:
        res = setup.session.run_window(state, 1, program=setup.program)
        inner.append(res.inner_iters[:, 0])
        state = res.state
        if res.done.all():
            break
    return types.SimpleNamespace(dist=np.asarray(state.dist),
                                 n_supersteps=res.n_supersteps,
                                 inner_iters=np.stack(inner, axis=1))


def _labels(setup):
    from scipy.sparse import csgraph

    from bench import reference as ref

    _, comp = csgraph.connected_components(
        ref.adjacency(setup.n, setup.src, setup.dst), directed=False)
    first = np.full(comp.max() + 1, setup.n)
    np.minimum.at(first, comp, np.arange(setup.n))
    return first[comp]


def compare(cell, setup, batches):
    want = _labels(setup)
    bad = np.concatenate([(b.dist != want).sum(axis=1) for b in batches])
    return {"label_mismatch": int(bad.sum())}, int((bad > 0).sum())


def control(cell, setup, batches):
    return [np.tile(np.arange(setup.n), (b.keys.size, 1)) for b in batches]
'''


def _digests(root):
    out = {}
    for base, _, names in os.walk(root):
        for name in names:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def _add(root, rel, text):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), rel  # new files only
    with open(path, "w") as f:
        f.write(text)


def _copy_benchmark(tmp_path) -> tuple[str, dict]:
    root = str(tmp_path)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(harness.ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root, _digests(os.path.join(root, "bench"))


def _add_cell(root, config: dict, traffic_name: str, traffic: dict, limits: dict,
              cell: str) -> dict:
    _add(root, f"bench/configs/{config['name']}.json", json.dumps(config))
    _add(root, f"bench/traffic/{traffic_name}.json", json.dumps(traffic))
    _add(root, f"bench/limits/{cell}.json", json.dumps(limits))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": config["name"], "source": config["source"],
                            "file": f"bench/configs/{config['name']}.json",
                            "reduced": [], "why": "a small graph"})
    spec["workloads"].append({"name": cell, "config": config["name"],
                              "traffic": traffic_name, "chips": 1,
                              "why": "a small cell"})
    next(m for m in spec["end_to_end"] if m["name"] == "teps")["workloads"].append(cell)
    return spec


def _write_spec(root, spec):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def test_new_config_traffic_and_metric_run_unchanged_harness(tmp_path):
    root, before = _copy_benchmark(tmp_path)
    cell = "kron-s8.bfs2"
    spec = _add_cell(root, NEW_CONFIG, "bfs2", NEW_TRAFFIC,
                     {"hop_mismatch": 0, "edge_count_mismatch": 0}, cell)
    _add(root, "bench/metrics/supersteps_per_batch.py", NEW_READER)
    spec["per_layer"].append({"name": "supersteps_per_batch", "unit": "supersteps",
                              "better": "lower", "source": "program_counter",
                              "layer": "session / engine", "moves": "teps",
                              "workloads": [cell]})
    _write_spec(root, spec)

    after = _digests(os.path.join(root, "bench"))
    assert all(after[k] == v for k, v in before.items())  # nothing edited

    plain = run_small(cell, root=root, cell=harness.load_cell(cell, root))
    assert plain["correct"] and set(plain["metrics"]) == {"teps", "setup_s"}
    traced = run_small(cell, trace=True, root=root, cell=harness.load_cell(cell, root))
    assert traced["correct"]
    assert traced["metrics"]["supersteps_per_batch"]["value"] >= 1
    assert traced["metrics"]["supersteps_per_batch"]["unit"] == "supersteps"


def test_new_generator_and_program_kind_run_unchanged_harness(tmp_path):
    root, before = _copy_benchmark(tmp_path)
    _add(root, "bench/generators/rings.py", RINGS_GENERATOR)
    _add(root, "bench/programs/wcc.py", WCC_PROGRAM)
    config = {"name": "rings-60", "source": "https://example.org/rings",
              "graph": {"generator": "rings", "n": 60, "rings": 3, "seed": 0},
              "partition": {"method": "bfs_grow", "n_parts": 4, "seed": 2},
              "engine": {"backend": "xla"}, "reduced": []}
    traffic = {"program": "wcc", "keys": 4, "keys_per_batch": 2,
               "pool": "degree_ge_1"}
    cell = "rings-60.wcc2"
    _write_spec(root, _add_cell(root, config, "wcc2", traffic,
                                {"label_mismatch": 0}, cell))

    after = _digests(os.path.join(root, "bench"))
    assert all(after[k] == v for k, v in before.items())  # nothing edited

    loaded = harness.load_cell(cell, root)
    result = run_small(cell, root=root, cell=loaded)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 4
    assert result["checks"] == {"label_mismatch": {"value": 0, "limit": 0}}
    assert result["metrics"]["teps"]["value"] > 0

    setup = harness.set_up(loaded, root)
    pool = harness.key_pool(loaded, setup.degree)
    done, _ = harness.run_window(setup, pool, 5, 0.01, harness.no_annotation)
    assert max(int(b.n_supersteps.max()) for b in done) > 1  # windows of 1
    control = [harness.Batch(b.keys, d, b.n_supersteps, b.inner_iters, b.edges, 0.0)
               for b, d in zip(done, loaded.kind.control(loaded, setup, done))]
    values, failed = harness.compare(loaded, setup, control)
    assert values["label_mismatch"] > 0 and failed == sum(b.keys.size for b in done)


def test_new_partition_method_runs_unchanged_harness(tmp_path):
    root, before = _copy_benchmark(tmp_path)
    _add(root, "bench/partitioners/round_robin.py", ROUND_ROBIN_PARTITIONER)
    config = dict(NEW_CONFIG, name="kron-s8-rr",
                  partition={"method": "round_robin", "n_parts": 4})
    cell = "kron-s8-rr.bfs2"
    _write_spec(root, _add_cell(root, config, "bfs2", NEW_TRAFFIC,
                                {"hop_mismatch": 0, "edge_count_mismatch": 0}, cell))

    after = _digests(os.path.join(root, "bench"))
    assert all(after[k] == v for k, v in before.items())  # nothing edited

    loaded = harness.load_cell(cell, root)
    setup = harness.set_up(loaded, root)
    assert np.array_equal(setup.session.pg.part_of_vertex, np.arange(setup.n) % 4)
    result = run_small(cell, root=root, cell=loaded)
    assert result["correct"] and result["failed"] == 0


def test_unknown_partition_method_is_refused(tmp_path):
    root, _ = _copy_benchmark(tmp_path)
    config = dict(NEW_CONFIG, name="kron-s8-metis",
                  partition={"method": "metis", "n_parts": 4, "seed": 2})
    cell = "kron-s8-metis.bfs2"
    _write_spec(root, _add_cell(root, config, "bfs2", NEW_TRAFFIC,
                                {"hop_mismatch": 0, "edge_count_mismatch": 0}, cell))
    with pytest.raises(SystemExit, match="no partitioners module 'metis'"):
        harness.set_up(harness.load_cell(cell, root), root)
