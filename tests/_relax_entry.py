"""One relax pass through the dense engine's kernel entry, for the tests.

``engine_relax`` builds the candidates as ``TraversalEngine`` does --
``where(frontier[:, src], program.relax(state[:, src], w), identity)`` over
a dst-sorted layout -- and reduces them with ``make_relax_fn``'s kernel in
interpret mode.  Monotone programs return ``combine(state,
segment_min(cand, dst))``; stationary programs return the accumulator
``segment_sum(cand, dst)``.  The plane fed to ``program.relax`` is
``layout.weights``.
"""

import jax.numpy as jnp

from repro.graph.program import SsspProgram
from repro.kernels.bfs_relax import make_relax_fn


def engine_relax(state, frontier, layout, *, program=None, block_n=512,
                 block_e=512):
    """``state``/``frontier`` are ``[N]`` or ``[S, N]``; so is the result."""
    prog = program or SsspProgram()
    squeeze = state.ndim == 1
    if squeeze:
        state, frontier = state[None], frontier[None]
    relax, blockmap = make_relax_fn(
        layout.dst, state.shape[1], reduce=prog.reduce, block_n=block_n,
        block_e=block_e, interpret=True,
    )
    src, w = jnp.asarray(layout.src), jnp.asarray(layout.weights)
    ident = prog.identity
    cand = jnp.where(frontier[:, src], prog.relax(state[:, src], w), ident)
    base = state if prog.reduce == "min" else jnp.full_like(state, ident)
    out = relax(blockmap, cand, base)
    return out[0] if squeeze else out
