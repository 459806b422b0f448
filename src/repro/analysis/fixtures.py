"""The seeded known-bad corpus: every bug class the analyzers exist for,
reconstructed, and required to be flagged.

Each fixture rebuilds one shipped-or-plausible defect -- including the PR 5
stale ``tobytes()`` layout-cache key and the PR 6 zero-size Pallas grid /
uninitialized output tile -- and runs it through the SAME checker the live
audit uses (never a fixture-only code path), asserting at least one finding
with the expected rule id and message substring.  ``--fixtures`` mode (and
``tests/test_analysis.py``) fails unless 100% of the corpus is flagged: the
proof that the green main audit is green because the tree is clean, not
because the checkers are blind.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.analysis.jaxpr_audit import (
    check_cache_key_fn,
    check_hot_path,
    check_pallas_grids,
    check_scopes,
    check_window_collectives,
)
from repro.analysis.lint import lint_source
from repro.dist.sharding import PARTS

_D = 4  # abstract mesh width of the SPMD fixtures


@dataclasses.dataclass(frozen=True)
class Fixture:
    name: str
    rule: str  # the rule that must fire
    must_match: str  # substring required in at least one finding's message
    description: str
    run: callable  # () -> list[Finding]


@dataclasses.dataclass(frozen=True)
class FixtureResult:
    fixture: Fixture
    findings: list
    flagged: bool


# -- JX04: the PR 5 bug -------------------------------------------------------


def _fx_stale_tobytes_cache():
    """PR 5's original layout-cache key: raw uncoerced ``tobytes()`` --
    dtype-sensitive AND lets two different maps alias one buffer."""
    legacy_key = lambda dmap, n_devices: (int(n_devices), dmap.tobytes())
    return check_cache_key_fn(legacy_key, "fixture/stale-tobytes-key")


# -- JX03: the PR 6 bug -------------------------------------------------------


def _legacy_block_dims(n: int, e: int, block_n: int, block_e: int):
    """PR 6's ``_block_dims`` WITHOUT the ``max(8, e)`` clamp: an empty edge
    shard yields ``e_pad == 0`` and a zero-size grid dimension."""
    bn = max(8, min(block_n, n))
    n_pad = -(-n // bn) * bn
    be = min(block_e, e)
    e_pad = -(-e // be) * be if be else 0
    return bn, be, n_pad, e_pad


def _fx_zero_size_grid():
    bn, be, n_pad, e_pad = _legacy_block_dims(16, 0, 512, 512)
    t = e_pad // be if be else 0  # 0: the degenerate inner grid dim

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def f(x):
        return pl.pallas_call(
            kern,
            grid=(n_pad // bn, t),
            in_specs=[pl.BlockSpec((1, 8), lambda i, j: (0, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i, j: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 8), jnp.float32),
        )(x)

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((1, 8), jnp.float32))
    return check_pallas_grids(closed, "fixture/zero-size-grid", expect_kernel=True)


# -- JX02: SPMD collective defects -------------------------------------------


def _spmd_jaxpr(body, n_outs_rep: int = 1):
    """Trace ``body`` under shard_map over an abstract parts mesh and return
    the mapped body's jaxpr (what ``check_window_collectives`` takes)."""
    mapped = jax.shard_map(
        body,
        mesh=AbstractMesh((_D,), (PARTS,)),
        in_specs=(P(None, PARTS),),
        out_specs=(P(None, PARTS),) + (P(),) * n_outs_rep,
        check_vma=False,
    )
    closed = jax.make_jaxpr(mapped)(jax.ShapeDtypeStruct((2, _D * 8), np.float32))
    (sm,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "shard_map"]
    return sm.params["jaxpr"]


_MINI_SIG = {"all_to_all": 1, "psum": 0, "pmax_boundary": 1, "pmax_closure": 0}
_MINI_EPILOGUE = {"psum": 1, "pmax": 0}


def _mini_window(x, *, drop_epilogue_psum: bool):
    """A minimal correctly-shaped window: superstep loop (globally-synced
    cond, one boundary pmax, one all_to_all) + a counter psum epilogue --
    which the dropped-psum variant omits, shipping per-device partials."""

    def cond(c):
        s, x, we = c
        return (s < 3) & (
            jax.lax.pmax((x > 0).any().astype(jnp.int32), PARTS) > 0
        )

    def step(c):
        s, x, we = c
        nst = jax.lax.pmax((x > 0).any().astype(jnp.int32), PARTS)
        recv = jax.lax.all_to_all(
            x.reshape(2, _D, -1), PARTS, split_axis=1, concat_axis=1, tiled=True
        ).reshape(x.shape)
        return s + 1, jnp.minimum(x, recv), we + nst

    _, x, we = jax.lax.while_loop(cond, step, (jnp.int32(0), x, jnp.int32(0)))
    if not drop_epilogue_psum:
        we = jax.lax.psum(we, PARTS)
    return x, we


def _fx_dropped_psum():
    body = _spmd_jaxpr(lambda x: _mini_window(x, drop_epilogue_psum=True))
    findings = check_window_collectives(
        body, _MINI_SIG, "fixture/dropped-psum", epilogue=_MINI_EPILOGUE
    )
    # the intact twin must pass through the same checker clean: the fixture
    # demonstrates the checker fires on the defect, not on the shape
    good = _spmd_jaxpr(lambda x: _mini_window(x, drop_epilogue_psum=False))
    clean = check_window_collectives(
        good, _MINI_SIG, "fixture/dropped-psum-control", epilogue=_MINI_EPILOGUE
    )
    assert not clean, f"control fixture must audit clean, got {clean}"
    return findings


_MINI_SIG_MIRRORED = {
    "all_to_all": 2, "psum": 0, "pmax_boundary": 1, "pmax_closure": 0
}


def _mini_window_mirrored(x, *, drop_mirror_sync: bool):
    """The hub-mirrored window shape: wire exchange plus the mirror->owner
    sync (two boundary all_to_alls, as the mirrored signature declares).
    The defect variant drops the sync -- mirrors accumulate hub aggregates
    that never reach their owners, while the engine still declares (and
    bills) the mirrored signature."""

    def cond(c):
        s, x, we = c
        return (s < 3) & (
            jax.lax.pmax((x > 0).any().astype(jnp.int32), PARTS) > 0
        )

    def step(c):
        s, x, we = c
        nst = jax.lax.pmax((x > 0).any().astype(jnp.int32), PARTS)
        recv = jax.lax.all_to_all(
            x.reshape(2, _D, -1), PARTS, split_axis=1, concat_axis=1, tiled=True
        ).reshape(x.shape)
        x = jnp.minimum(x, recv)
        if not drop_mirror_sync:
            mrecv = jax.lax.all_to_all(
                x.reshape(2, _D, -1), PARTS,
                split_axis=1, concat_axis=1, tiled=True,
            ).reshape(x.shape)
            x = jnp.minimum(x, mrecv)
        return s + 1, x, we + nst

    _, x, we = jax.lax.while_loop(cond, step, (jnp.int32(0), x, jnp.int32(0)))
    return x, jax.lax.psum(we, PARTS)


def _fx_dropped_mirror_sync():
    body = _spmd_jaxpr(
        lambda x: _mini_window_mirrored(x, drop_mirror_sync=True)
    )
    findings = check_window_collectives(
        body, _MINI_SIG_MIRRORED, "fixture/dropped-mirror-sync",
        epilogue=_MINI_EPILOGUE,
    )
    # the intact mirrored twin must pass the mirrored declaration clean
    good = _spmd_jaxpr(
        lambda x: _mini_window_mirrored(x, drop_mirror_sync=False)
    )
    clean = check_window_collectives(
        good, _MINI_SIG_MIRRORED, "fixture/dropped-mirror-sync-control",
        epilogue=_MINI_EPILOGUE,
    )
    assert not clean, f"control fixture must audit clean, got {clean}"
    return findings


def _fx_conditional_collective():
    def body(x):
        def cond(c):
            s, x = c
            return (s < 2) & (
                jax.lax.pmax((x > 0).any().astype(jnp.int32), PARTS) > 0
            )

        def step(c):
            s, x = c
            nst = jax.lax.pmax((x > 0).any().astype(jnp.int32), PARTS)
            # BUG: the exchange is skipped on quiet devices -- busy devices
            # enter the collective alone and deadlock
            x = jax.lax.cond(
                nst > 0,
                lambda v: jax.lax.all_to_all(
                    v.reshape(2, _D, -1), PARTS,
                    split_axis=1, concat_axis=1, tiled=True,
                ).reshape(v.shape),
                lambda v: v,
                x,
            )
            return s + 1, x

        _, x = jax.lax.while_loop(cond, step, (jnp.int32(0), x))
        return x, jax.lax.psum(x.sum(), PARTS)

    return check_window_collectives(
        _spmd_jaxpr(body), _MINI_SIG, "fixture/conditional-collective",
        epilogue=_MINI_EPILOGUE,
    )


def _fx_unsynced_loop():
    def body(x):
        def cond(c):
            s, x = c
            # BUG: device-local condition around a collective body
            return (s < 3) & (x > 0).any()

        def step(c):
            s, x = c
            return s + 1, x - jax.lax.psum(x.sum(), PARTS) * 0 - 1.0

        _, x = jax.lax.while_loop(cond, step, (jnp.int32(0), x))
        return x, jax.lax.psum(x.sum(), PARTS)

    return check_window_collectives(
        _spmd_jaxpr(body), _MINI_SIG, "fixture/unsynced-loop",
        epilogue=_MINI_EPILOGUE,
    )


# -- JX01: host interop -------------------------------------------------------


def _fx_host_callback():
    def bad_window(dist):
        jax.debug.print("frontier size {}", (dist < np.inf).sum())
        return dist * 2.0

    closed = jax.make_jaxpr(bad_window)(jax.ShapeDtypeStruct((8,), jnp.float32))
    return check_hot_path(closed, "fixture/host-callback")


# -- JX06: device scopes ------------------------------------------------------


def _fx_unscoped_op():
    """The real dense window with one op added outside any scope: a
    frontier-size counter taken after the superstep loop."""
    from repro.analysis.jaxpr_audit import default_audit_graph
    from repro.graph.config import EngineConfig
    from repro.graph.traversal import TraversalEngine

    engine = TraversalEngine(
        default_audit_graph(), config=EngineConfig(backend="xla")
    )

    def window(dist, frontier, nst0, consts):
        out = engine._window_impl(dist, frontier, nst0, 3, consts)
        return out, out[0].frontier.sum()

    n, dtype = engine.n, engine.program.dtype
    sds = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(window)(
        sds((2, n), dtype), sds((2, n), np.bool_), sds((2,), np.int32),
        jax.tree.map(lambda a: sds(a.shape, a.dtype), engine._consts),
    )
    return check_scopes(closed, "fixture/unscoped-op")


# -- AL01/AL02/AL03/AL04: source-level reconstructions ------------------------

_SRC_NUMPY_IN_TRACED = '''\
import numpy as np
import jax.numpy as jnp


def window_step(dist, frontier):
    mask = np.asarray(frontier)
    if frontier.any():
        dist = dist + float(dist.min())
    return jnp.where(mask, dist, 0.0)
'''

_SRC_UNBOUNDED_CACHE = '''\
_LAYOUTS = {}


def get_layout(key, build):
    if key not in _LAYOUTS:
        _LAYOUTS[key] = build()
    return _LAYOUTS[key]
'''

_SRC_UNINIT_KERNEL = '''\
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _relax_kernel(cnt_ref, dst_ref, cand_ref, o_ref):
    oi = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t < cnt_ref[oi])
    def _compute():
        o_ref[...] = jnp.minimum(o_ref[...], cand_ref[...])
'''

_SRC_BYTES_KEY = '''\
def layout_cache_key(device_of_part, n_devices):
    return (int(n_devices), device_of_part.tobytes())
'''


def _fx_numpy_in_traced():
    return lint_source(
        _SRC_NUMPY_IN_TRACED, "fixture/numpy_in_traced.py",
        traced_overrides=[("window_step", ("dist", "frontier"))],
    )


def _fx_unbounded_cache():
    return lint_source(_SRC_UNBOUNDED_CACHE, "fixture/unbounded_cache.py")


def _fx_uninitialized_kernel():
    return lint_source(_SRC_UNINIT_KERNEL, "fixture/uninit_kernel.py")


def _fx_bytes_key():
    return lint_source(_SRC_BYTES_KEY, "fixture/bytes_key.py")


ALL_FIXTURES = (
    Fixture(
        "stale-tobytes-cache-key", "JX04", "alias",
        "PR 5's raw-tobytes layout-cache key (dtype-blind, buffer-aliasing)",
        _fx_stale_tobytes_cache,
    ),
    Fixture(
        "zero-size-grid", "JX03", "grid dimension",
        "PR 6's unclamped _block_dims: empty edge shard -> 0-size grid dim",
        _fx_zero_size_grid,
    ),
    Fixture(
        "dropped-psum", "JX02", "epilogue",
        "window returns a per-device counter without its epilogue psum",
        _fx_dropped_psum,
    ),
    Fixture(
        "dropped-mirror-sync", "JX02", "superstep-boundary collectives",
        "mirrored engine whose mirror->owner sync all_to_all was dropped "
        "while the signature still declares it",
        _fx_dropped_mirror_sync,
    ),
    Fixture(
        "conditional-collective", "JX02", "branch-dependent",
        "exchange wrapped in lax.cond: quiet devices skip the collective",
        _fx_conditional_collective,
    ),
    Fixture(
        "unsynced-loop", "JX02", "device-local",
        "collective inside a loop whose condition is not globally synced",
        _fx_unsynced_loop,
    ),
    Fixture(
        "host-callback", "JX01", "debug_print",
        "jax.debug.print traced into the superstep hot path",
        _fx_host_callback,
    ),
    Fixture(
        "unscoped-op", "JX06", "'reduce_sum'",
        "real dense window with a frontier count added after the loop, "
        "outside every device scope",
        _fx_unscoped_op,
    ),
    Fixture(
        "numpy-in-traced-fn", "AL01", "numpy ops force a host round-trip",
        "np.asarray / float() / Python if over traced window arguments",
        _fx_numpy_in_traced,
    ),
    Fixture(
        "unbounded-cache", "AL02", "without a bound",
        "module-level dict cache growing forever",
        _fx_unbounded_cache,
    ),
    Fixture(
        "uninitialized-kernel-tile", "AL03", "base-initializes",
        "PR 6 kernel shape with the first-step output-tile init removed",
        _fx_uninitialized_kernel,
    ),
    Fixture(
        "bytes-cache-key-source", "AL04", "tobytes",
        "source-level twin of the stale cache key: tobytes without "
        "shape/dtype",
        _fx_bytes_key,
    ),
)


def run_fixtures() -> list[FixtureResult]:
    """Run the whole corpus; a fixture is flagged iff some finding carries
    its rule id AND its pinned message substring."""
    results = []
    for fx in ALL_FIXTURES:
        findings = fx.run()
        flagged = any(
            f.rule == fx.rule and fx.must_match in f.message for f in findings
        )
        results.append(FixtureResult(fx, findings, flagged))
    return results
