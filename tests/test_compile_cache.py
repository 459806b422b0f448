"""``repro.compile_cache``: the persistent cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, or else to one fixed gitignored path in
the checkout.  Each case runs in a child: JAX reads the variable when it is
imported."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from repro.compile_cache import DEFAULT_DIR, ENV_VAR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _child(env_dir: str | None, compile_: bool) -> list[str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop(ENV_VAR, None)
    if env_dir is not None:
        env[ENV_VAR] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", CHILD.format(compile=compile_)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


@pytest.mark.parametrize("env_set", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_location(env_set, tmp_path):
    if env_set:
        target = str(tmp_path / "cache")
        path, configured = _child(target, compile_=True)
        assert path == configured == target
        assert os.listdir(target), "nothing was written to the cache"
    else:
        path, configured = _child(None, compile_=False)
        assert path == configured == DEFAULT_DIR
        assert os.path.dirname(DEFAULT_DIR) == ROOT
        with open(os.path.join(ROOT, ".gitignore")) as f:
            ignored = f.read().split()
        assert os.path.basename(DEFAULT_DIR) + "/" in ignored
