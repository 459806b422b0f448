"""Production mesh definition.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax import).

Axis semantics:
  pod   -- across-pod data parallelism over DCN (params replicated per pod)
  data  -- in-pod FSDP/batch axis (256-chip pod: 16)
  model -- tensor/expert parallel axis (16)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh with model=1.
    Used by the CPU train/serve demos and tests."""
    n = len(jax.devices())
    return jax.make_mesh(
        (n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
