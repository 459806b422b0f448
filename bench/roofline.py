"""The least bytes of the relax kernel's passes, counted from the problem.

A pass reduces one edge plane (local or remote) into the ``S`` state rows.
Whatever the kernel's grid, a pass has to read each arc of its plane once
(its destination id, and either its source id plus its weight where the
program reads one, or its ``S`` gathered candidates, whichever is fewer
bytes) and read and write the ``S`` state rows once.  The bound is memory:
the kernel does a compare and a select per candidate, far below the chip's
peak operations.  So a kernel that skips empty tiles raises its share, and
one that stops doing the work cannot.
"""

from __future__ import annotations

import numpy as np

WORD = 4  # bytes of an index, a weight and a state element (int32 / f32)


def pass_bytes(n_arcs: int, n_vertices: int, s_batch: int, weighted: bool) -> int:
    """Least bytes of one relax pass over ``n_arcs`` into ``s_batch`` rows."""
    per_arc = WORD + min(WORD * (1 + int(weighted)), WORD * s_batch)
    return n_arcs * per_arc + 2 * WORD * s_batch * n_vertices


def passes(n_supersteps, inner_iters, stationary: bool) -> tuple[int, int]:
    """(local passes, remote passes) of one batched run: one local pass per
    closure iteration (the batch iterates while any row does; one per
    superstep for a stationary program) and one remote pass per superstep."""
    m = int(np.max(n_supersteps))
    if stationary:
        return m, m
    return int(np.asarray(inner_iters)[:, :m].max(axis=0).sum()), m


def run_bytes(
    local_arcs: int, remote_arcs: int, n_vertices: int, s_batch: int,
    weighted: bool, n_supersteps, inner_iters, stationary: bool,
) -> int:
    """Least bytes of every relax pass of one batched run."""
    n_local, n_remote = passes(n_supersteps, inner_iters, stationary)
    return n_local * pass_bytes(
        local_arcs, n_vertices, s_batch, weighted
    ) + n_remote * pass_bytes(remote_arcs, n_vertices, s_batch, weighted)
