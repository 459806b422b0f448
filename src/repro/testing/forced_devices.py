"""Subprocess-safe multi-device runs for tests and benches.

``--xla_force_host_platform_device_count`` only takes effect before jax
initializes its backends, so any process that already imported jax (the
pytest session, the bench parent) cannot grow devices in place.  The one
shared recipe lives here: spawn a child with the flag *appended* to
``XLA_FLAGS`` (outer environments keep flags they already set) and ``src``
prepended to ``PYTHONPATH`` (so the child resolves ``repro`` regardless of
how the parent was invoked).  ``tests/conftest.py`` and
``benchmarks/traversal_bench.py`` both route through this function.

The child always runs with ``JAX_PLATFORMS=cpu``: its devices are forced
host devices, a CPU rehearsal by construction.  An accelerator belongs to
one process at a time, so a child must never wait on a chip the parent
holds.
"""

from __future__ import annotations

import os
import subprocess
import sys

_SRC = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_forced_devices(
    script_path: str,
    *args: str,
    n_devices: int = 8,
    timeout: float = 900.0,
) -> str:
    """Run ``script_path`` under ``n_devices`` forced host devices.

    Returns the child's stdout; raises ``RuntimeError`` carrying both
    streams on a non-zero exit.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, script_path, *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"forced-device child {os.path.basename(script_path)} exited "
            f"{proc.returncode}\n--- stdout ---\n{proc.stdout}"
            f"\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout
