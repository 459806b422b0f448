from repro.kernels.bfs_relax.ops import (
    RELAX_BACKENDS,
    make_relax_fn,
    relax_blockmap_call,
    validate_backend,
)
from repro.kernels.bfs_relax.ref import reference_bfs_relax

__all__ = [
    "RELAX_BACKENDS",
    "make_relax_fn",
    "relax_blockmap_call",
    "reference_bfs_relax",
    "validate_backend",
]
