"""What the analyzers analyze: the audit matrix and the traced-function
registry.

``TRACED_FUNCTIONS`` names every function whose body is traced into a jitted
program (directly or via ``shard_map``/``pallas_call``) together with which
of its parameters are traced arrays.  The AST lint layer (rule AL01) holds
exactly these functions to the traced-purity rules -- host-side helpers can
use numpy freely, the hot path cannot.  Functions decorated with ``jax.jit``
(or ``functools.partial(jax.jit, ...)``) are picked up automatically by
``lint``; this registry covers the ones jitted at a distance (bound methods
jitted in ``__init__``, ``shard_map`` bodies, Pallas kernels).

``AUDIT_BACKENDS`` / ``AUDIT_MESH_WIDTH`` pin the jaxpr auditor's matrix:
every builtin program is traced dense and mesh, per backend, on every run of
``python -m repro.analysis``.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class TracedFn:
    """One traced function: file suffix + function name + traced params."""

    file_suffix: str  # path suffix under src/, e.g. "graph/traversal.py"
    name: str  # the def's name (unique within its file)
    array_params: tuple  # parameter names that arrive as tracers
    note: str = ""


#: functions traced at a distance -- the AL01 registry (auto-detection covers
#: directly ``@jax.jit``-decorated defs)
TRACED_FUNCTIONS = (
    TracedFn(
        "graph/traversal.py",
        "_window_impl",
        ("dist", "frontier", "nst0", "consts"),
        "jitted in TraversalEngine.__init__ (static_argnums=3)",
    ),
    TracedFn(
        "graph/mesh_exchange.py",
        "_body",
        (
            "dist", "frontier", "nst0",
            "lsrc", "ldst", "lw", "ldeg", "lvalid", "part_of_pos",
            "rsrc", "rw", "rslot", "rdeg", "rvalid", "recv_idx",
            "msrc", "mw", "mslot", "mdeg", "mvalid", "mrecv_idx",
        ),
        "shard_map body; keyword-only params are static",
    ),
    TracedFn(
        "graph/traversal.py",
        "_backfill_impl",
        ("dist", "frontier", "nst", "rows", "f_dist", "f_frontier", "live",
         "ident"),
        "jitted at a distance via _BACKFILL_FN_CACHE (serving row surgery)",
    ),
    TracedFn(
        "graph/deltas.py",
        "_reactivate_rows",
        ("dist", "frontier", "idx", "identity"),
        "delta-merge entry point: inserted-source frontier reactivation "
        "(directly @jax.jit, registered explicitly as a mutation seam)",
    ),
    TracedFn(
        "kernels/bfs_relax/ops.py",
        "relax_blockmap_call",
        ("start", "cnt", "dst", "cand", "base"),
        "called inside jitted windows",
    ),
    TracedFn(
        "kernels/bfs_relax/kernel.py",
        "_kernel_blockmap",
        ("start_ref", "cnt_ref", "dst_ref", "cand_ref", "base_ref", "o_ref"),
        "Pallas kernel (generic relax)",
    ),
)

#: backends the auditor traces every program under.  ``pallas`` lowers
#: identically to ``pallas-interpret`` at trace time (interpret is a call
#: param, not a different jaxpr shape), so auditing interpret covers both.
AUDIT_BACKENDS = ("xla", "pallas-interpret")

#: abstract mesh width for the SPMD audits (any D >= 2 exercises the same
#: collective structure; 4 keeps padded shard shapes interesting)
AUDIT_MESH_WIDTH = 4

#: hub threshold the auditor uses for the mirrored mesh audits -- low enough
#: that the default audit graph has qualifying hubs (a zero-hub threshold
#: would silently audit the unmirrored trace)
AUDIT_MIRROR_DEGREE = 2
