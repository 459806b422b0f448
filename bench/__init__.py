"""Graph benchmark: one cell of BENCHMARK.json per run (bench/run.py).

What belongs to one configuration, traffic mix, program kind, graph
generator or metric sits in a file of its own, found by its name:
``load_module`` loads ``bench/<folder>/<name>.py`` of a checkout.
"""

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(folder: str, name: str, root: str = ROOT):
    """``bench/<folder>/<name>.py`` of ``root``, loaded by its path."""
    path = os.path.join(root, "bench", folder, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {folder} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
