"""One frozen configuration surface for every engine-shaped constructor.

``EngineConfig`` holds every engine knob (mesh, backend, block sizes,
window, relayout, ...) in one immutable value.  It is the only way to set
them: ``TraversalEngine``, ``get_engine``, ``ElasticBSPExecutor`` and
``TraversalService`` each take it as their one engine argument, and it
travels intact from ``open_session`` down through ``bsp.run_program``, the
elastic executor, and the serving layer.

``REPORT_SCHEMA_VERSION`` + ``versioned_report`` define the shared
``asdict()`` surface of ``TraversalResult`` / ``ExecutionReport`` /
``ServiceReport`` (the stability contract is documented in
``graph/__init__``): every dict carries ``schema_version`` and ``kind``
first, then the result's fields by name, so consumers key on names -- never
on positional field order, which each of those types has historically grown.
"""

from __future__ import annotations

import dataclasses
from typing import Any

#: Version of the shared report-dict surface.  Bump when a field is renamed
#: or removed; adding fields is backward compatible and does NOT bump.
REPORT_SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Every cross-layer engine knob, in one frozen value.

    ``mesh`` is a ``jax.sharding.Mesh`` (or None for the dense single-device
    engine); typed ``Any`` so importing this module never imports jax.
    """

    mesh: Any = None
    backend: str = "xla"
    mirror_degree: int | None = None
    m_max: int = 512
    window: int = 8  # supersteps per launched window (elastic / serving)
    relayout: bool = False  # elastic executor: follow the plan with devices
    block_n: int = 512  # Pallas relax-kernel block sizes
    block_e: int = 512
    collect_subgraphs: bool = False

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


def versioned_report(kind: str, fields: dict) -> dict:
    """The shared report-dict shape: schema version + kind + named fields."""
    out = {"schema_version": REPORT_SCHEMA_VERSION, "kind": str(kind)}
    out.update(fields)
    return out
