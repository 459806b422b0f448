"""Layer 1: the jaxpr auditor.

Abstractly traces the dense ``TraversalEngine`` window and the mesh
``MeshTraversalProgram._body`` for every builtin program x backend --
the mesh side over ``jax.sharding.AbstractMesh``, so the full SPMD trace
(collectives, Pallas grids) is walked with ZERO real mesh devices, i.e.
inside the single-device tier1 CI job -- and checks the ``ClosedJaxpr``
against the engine's declared invariants:

  JX01  no host callbacks / transfers / debug prints on the hot path,
  JX02  collective balance inside ``shard_map``: every collective names the
        ``parts`` axis; per-superstep count and order match the program's
        ``collective_signature()``; loop conds containing collectives are
        themselves globally synced; ``lax.cond`` branches agree on their
        collective footprint (a mismatched or conditionally-skipped
        collective is a deadlock/corruption at D > 1),
  JX03  every Pallas grid dimension is provably >= 1 (the ``_block_dims``
        zero-grid bug class) and a kernel backend actually lowered to
        ``pallas_call``,
  JX04  cache keys are canonical (dtype/shape aliases of one device map hit
        one entry, distinct maps never collide) and a scripted
        relayout/window sweep stays within the PR 5 cache policy,
  JX05  the program's ``identity`` is the dtype-derived identity of its
        ``reduce`` (what the Pallas kernels pad with) and is a numerical
        fixed point of ``relax``/``combine``,
  JX06  every operation of the window sits under one plane scope and one
        role scope (``repro.spans``), so none of its device time reads as
        unscoped.

All checks return ``Finding`` lists; ``audit_tree`` runs the whole matrix.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis.registry import (
    AUDIT_BACKENDS,
    AUDIT_MESH_WIDTH,
    AUDIT_MIRROR_DEGREE,
)
from repro.dist.sharding import PARTS
from repro.graph.mesh_exchange import (
    MESH_SUPERSTEP_COND,
    MESH_WINDOW_EPILOGUE,
    abstract_window_jaxpr,
    build_window_consts,
    window_cache_key,
)
from repro.graph.partition import (
    _LAYOUT_CACHE_MAX,
    contiguous_device_map,
    mesh_edge_layout,
)
from repro.graph.program import (
    BUILTIN_PROGRAMS,
    validate_collective_signature,
    validate_program,
)
from repro.graph.structs import mesh_layout_key
from repro.kernels.bfs_relax.ops import _identity_scalar
from repro.spans import scope_of

#: primitives that leave the device / re-enter Python -- none may appear in
#: a traced window (rule JX01)
HOST_INTEROP_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call", "host_callback", "callback", "infeed", "outfeed",
    "device_put",
})

#: named-axis primitives the balance checker accounts for (rule JX02): the
#: name jax emits -> the name collective signatures count.  With replication
#: checks on (``check_vma=True``), ``lax.psum`` inside ``shard_map`` traces
#: as ``psum_invariant``; it is the same collective.
COLLECTIVE_PRIMS = {
    name: name
    for name in (
        "psum", "pmax", "pmin", "all_to_all", "all_gather", "ppermute",
        "psum_scatter", "pgather", "reduce_scatter",
    )
} | {"psum_invariant": "psum"}


# -- jaxpr walking -----------------------------------------------------------


def _as_jaxpr(obj):
    """Unwrap ClosedJaxpr -> Jaxpr; pass raw Jaxpr through; else None."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def sub_jaxprs(eqn):
    """(sub_jaxpr, tag) pairs nested in an eqn's params, in param order."""
    out = []
    for name, val in sorted(eqn.params.items()):
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for i, v in enumerate(vals):
            sub = _as_jaxpr(v)
            if sub is not None:
                out.append((sub, f"{eqn.primitive.name}.{name}[{i}]"))
    return out


def iter_eqns(jaxpr, path=()):
    """Yield every (eqn, path) in the jaxpr, depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub, tag in sub_jaxprs(eqn):
            yield from iter_eqns(sub, path + (tag,))


def _collective_axes(eqn):
    """Normalized tuple of axis names a collective eqn binds."""
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if not isinstance(axes, (list, tuple)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collectives_in(jaxpr) -> Counter:
    """Recursive Counter of collective primitive names in a (Closed)Jaxpr."""
    jaxpr = _as_jaxpr(jaxpr)
    return Counter(
        COLLECTIVE_PRIMS[e.primitive.name]
        for e, _ in iter_eqns(jaxpr)
        if e.primitive.name in COLLECTIVE_PRIMS
    )


# -- JX01: host interop -------------------------------------------------------


def check_hot_path(traced, label: str) -> list[Finding]:
    """No host-interop primitive anywhere in the traced window."""
    findings = []
    for eqn, path in iter_eqns(_as_jaxpr(traced)):
        name = eqn.primitive.name
        if name in HOST_INTEROP_PRIMS:
            at = "/".join(path) or "top level"
            findings.append(Finding(
                "JX01", label,
                f"host-interop primitive '{name}' on the hot path (at {at})",
            ))
    return findings


# -- JX06: device scopes ------------------------------------------------------


def check_scopes(traced, label: str) -> list[Finding]:
    """Every operation of the traced window sits under one plane scope and
    one role scope.  An equation's name stack is relative to the equation
    that holds its jaxpr (a ``while``, ``pjit``, ``shard_map``), so stacks
    are joined on the way down; a ``pallas_call`` is one operation."""
    unscoped = Counter()

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            names = outer + tuple(
                n for n in str(eqn.source_info.name_stack).split("/") if n
            )
            subs = () if eqn.primitive.name == "pallas_call" else sub_jaxprs(eqn)
            for sub, _ in subs:
                walk(sub, names)
            if not subs and scope_of(names) is None:
                unscoped[(eqn.primitive.name, "/".join(names) or "top level")] += 1

    walk(_as_jaxpr(traced), ())
    return [
        Finding(
            "JX06", label,
            f"{count} '{prim}' op(s) at '{at}' outside one plane and one "
            "role scope (repro.spans): their device time reads as unscoped",
        )
        for (prim, at), count in sorted(unscoped.items())
    ]


# -- JX03: Pallas grids -------------------------------------------------------


def grid_findings(grid, label: str, context: str = "pallas_call") -> list[Finding]:
    """Every grid dimension must be a provably positive static int."""
    findings = []
    for i, dim in enumerate(tuple(grid)):
        if not isinstance(dim, (int, np.integer)) or int(dim) < 1:
            findings.append(Finding(
                "JX03", label,
                f"{context} grid dimension {i} is {dim!r}, not a static "
                "int >= 1: zero-size grids skip the kernel's first-step "
                "output-tile init and return garbage tiles",
            ))
    return findings


def check_pallas_grids(traced, label: str, *, expect_kernel: bool = False) -> list[Finding]:
    """Audit every ``pallas_call`` grid in the trace (and, for kernel
    backends, that at least one exists -- a silent XLA fallback would pass
    every other check while benchmarking the wrong path)."""
    findings = []
    seen = 0
    for eqn, path in iter_eqns(_as_jaxpr(traced)):
        if eqn.primitive.name != "pallas_call":
            continue
        seen += 1
        grid = eqn.params["grid_mapping"].grid
        at = "/".join(path) or "top level"
        findings.extend(grid_findings(grid, label, context=f"pallas_call at {at}"))
    if expect_kernel and seen == 0:
        findings.append(Finding(
            "JX03", label,
            "kernel backend selected but no pallas_call primitive in the "
            "trace -- the window silently fell back to XLA segment ops",
        ))
    return findings


# -- JX02: collective balance -------------------------------------------------


def _axis_findings(body, label: str) -> list[Finding]:
    findings = []
    for eqn, path in iter_eqns(body):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        axes = _collective_axes(eqn)
        if axes != (PARTS,):
            at = "/".join(path) or "top level"
            findings.append(Finding(
                "JX02", label,
                f"collective '{eqn.primitive.name}' at {at} binds axes "
                f"{axes!r}; every mesh collective must bind exactly "
                f"('{PARTS}',)",
            ))
    return findings


def _branch_findings(body, label: str) -> list[Finding]:
    """lax.cond branches must agree on their collective footprint."""
    findings = []
    for eqn, path in iter_eqns(body):
        if eqn.primitive.name != "cond":
            continue
        per_branch = [
            collectives_in(b) for b in eqn.params.get("branches", ())
        ]
        if per_branch and any(c != per_branch[0] for c in per_branch[1:]):
            at = "/".join(path) or "top level"
            findings.append(Finding(
                "JX02", label,
                f"cond at {at} has branch-dependent collectives "
                f"{[dict(c) for c in per_branch]}: a conditionally-skipped "
                "collective deadlocks devices that took the other branch",
            ))
    return findings


def _loop_sync_findings(body, label: str) -> list[Finding]:
    """A while whose body runs collectives needs a globally-synced cond:
    otherwise per-device iteration counts diverge and the body's collective
    deadlocks."""
    findings = []
    for eqn, path in iter_eqns(body):
        if eqn.primitive.name != "while":
            continue
        in_body = collectives_in(eqn.params["body_jaxpr"])
        in_cond = collectives_in(eqn.params["cond_jaxpr"])
        if in_body and not in_cond:
            at = "/".join(path) or "top level"
            findings.append(Finding(
                "JX02", label,
                f"while at {at} runs collectives {dict(in_body)} in its "
                "body but its condition is device-local: iteration counts "
                "can diverge across devices",
            ))
    return findings


def check_window_collectives(
    shard_body,
    signature: dict,
    label: str,
    *,
    epilogue: dict = MESH_WINDOW_EPILOGUE,
    cond_sig: dict = MESH_SUPERSTEP_COND,
) -> list[Finding]:
    """Check a shard_map-mapped window body against a declared signature.

    ``shard_body`` is the (Closed)Jaxpr the shard_map maps; ``signature`` the
    per-superstep expectation (``VertexProgram.collective_signature()``
    shape); ``epilogue``/``cond_sig`` the window-level constants.  Reused
    verbatim by the known-bad fixture corpus, so the checker that gates CI is
    the checker the fixtures prove can fire.
    """
    body = _as_jaxpr(shard_body)
    findings = []
    findings += _axis_findings(body, label)
    findings += _branch_findings(body, label)
    findings += _loop_sync_findings(body, label)

    whiles = [e for e in body.eqns if e.primitive.name == "while"]
    if len(whiles) != 1:
        findings.append(Finding(
            "JX02", label,
            f"expected exactly one outer superstep while_loop at the "
            f"shard_map body's top level, found {len(whiles)}",
        ))
        return findings
    outer = whiles[0]

    # epilogue: collectives at body level outside the superstep loop
    epi = Counter()
    for eqn in body.eqns:
        if eqn is outer:
            continue
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            epi[COLLECTIVE_PRIMS[eqn.primitive.name]] += 1
        else:
            for sub, _ in sub_jaxprs(eqn):
                epi += collectives_in(sub)
    if dict(epi) != {k: v for k, v in epilogue.items() if v}:
        findings.append(Finding(
            "JX02", label,
            f"window epilogue collectives {dict(epi)} != declared "
            f"{epilogue}: a dropped counter psum ships per-device partial "
            "counters as if they were global",
        ))

    # superstep cond: the global any-active sync
    cond_c = collectives_in(outer.params["cond_jaxpr"])
    if dict(cond_c) != {k: v for k, v in cond_sig.items() if v}:
        findings.append(Finding(
            "JX02", label,
            f"superstep-loop condition collectives {dict(cond_c)} != "
            f"declared {cond_sig}",
        ))

    # superstep body: boundary-level sequence vs the nested closure loop
    sbody = _as_jaxpr(outer.params["body_jaxpr"])
    boundary_seq = []
    closure = Counter()
    for eqn in sbody.eqns:
        if eqn.primitive.name == "while":
            closure += collectives_in(eqn.params["cond_jaxpr"])
            closure += collectives_in(eqn.params["body_jaxpr"])
            continue
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            boundary_seq.append(COLLECTIVE_PRIMS[eqn.primitive.name])
            continue
        for sub, _ in sub_jaxprs(eqn):
            boundary_seq.extend(
                COLLECTIVE_PRIMS[e.primitive.name]
                for e, _ in iter_eqns(sub)
                if e.primitive.name in COLLECTIVE_PRIMS
            )

    bc = Counter(boundary_seq)
    expected_boundary = {
        "pmax": signature["pmax_boundary"],
        "psum": signature["psum"],
        "all_to_all": signature["all_to_all"],
    }
    if dict(bc) != {k: v for k, v in expected_boundary.items() if v}:
        findings.append(Finding(
            "JX02", label,
            f"superstep-boundary collectives {dict(bc)} != declared "
            f"{expected_boundary} (from collective_signature())",
        ))
    else:
        # order: every boundary sync pmax precedes the value exchange
        first_a2a = boundary_seq.index("all_to_all") if "all_to_all" in boundary_seq else len(boundary_seq)
        if any(n == "pmax" for n in boundary_seq[first_a2a:]):
            findings.append(Finding(
                "JX02", label,
                f"boundary collective order {boundary_seq}: sync pmaxes "
                "must precede the all_to_all exchange",
            ))

    if dict(closure) != ({"pmax": signature["pmax_closure"]} if signature["pmax_closure"] else {}):
        findings.append(Finding(
            "JX02", label,
            f"local-closure loop collectives {dict(closure)} != declared "
            f"{{'pmax': {signature['pmax_closure']}}}: the closure may only "
            "sync its convergence bit",
        ))
    return findings


def check_mesh_trace(
    closed, program, label: str, *, mirrored: bool = False
) -> list[Finding]:
    """Full JX02 pass over an ``abstract_window_jaxpr`` trace: locate the
    shard_map and check its body against the program's declaration.

    ``mirrored`` selects the hub-mirroring variant of the declared
    signature (one extra ``all_to_all``: the mirror->owner sync) -- pass it
    iff the traced layout has a non-empty mirror plane, so a trace that
    runs the mirror sync without declaring it (or vice versa) fails the
    boundary-count check.
    """
    sms = [e for e, _ in iter_eqns(closed.jaxpr) if e.primitive.name == "shard_map"]
    if len(sms) != 1:
        return [Finding(
            "JX02", label,
            f"expected exactly one shard_map in the mesh window trace, "
            f"found {len(sms)}",
        )]
    sig = validate_collective_signature(program, mirrored=mirrored)
    return check_window_collectives(sms[0].params["jaxpr"], sig, label)


# -- JX05: reduction identity -------------------------------------------------


def check_identity(program, label: str) -> list[Finding]:
    """The program's identity must equal the kernel layer's dtype-derived
    identity and be a numerical fixed point of relax/combine."""
    findings = []
    program = validate_program(program)
    ident = program.identity
    expected = _identity_scalar(program.reduce, np.dtype(program.dtype))
    same_val = (ident == expected) or (
        np.issubdtype(np.dtype(program.dtype), np.floating)
        and np.isinf(ident) and np.isinf(expected) and ident > 0 and expected > 0
    )
    if not same_val or np.asarray(ident).dtype != np.asarray(expected).dtype:
        findings.append(Finding(
            "JX05", label,
            f"identity {ident!r} != the dtype-derived identity "
            f"{expected!r} of reduce='{program.reduce}' over "
            f"{np.dtype(program.dtype).name} -- Pallas padding and engine "
            "padding would disagree",
        ))
        return findings
    if np.issubdtype(np.dtype(program.dtype), np.floating):
        samples = np.asarray([0.0, 1.5, 7.0], dtype=program.dtype)
    else:
        samples = np.asarray([0, 1, 7], dtype=program.dtype)
    ivec = jnp.full(samples.shape, ident, dtype=np.dtype(program.dtype))
    comb = np.asarray(program.combine(ivec, jnp.asarray(samples)))
    if not np.array_equal(comb, samples):
        findings.append(Finding(
            "JX05", label,
            f"combine(identity, x) != x (got {comb.tolist()} for "
            f"{samples.tolist()}): padded lanes would corrupt reductions",
        ))
    w = jnp.asarray(np.asarray([0.5, 1.0, 2.0], dtype=np.float32))
    relaxed = np.asarray(program.relax(ivec, w))
    if not np.array_equal(relaxed, np.asarray(ivec)):
        findings.append(Finding(
            "JX05", label,
            f"relax(identity, w) != identity (got {relaxed.tolist()}): "
            "padded edges would emit live messages",
        ))
    return findings


# -- JX04: cache keys + recompile budget -------------------------------------


def check_cache_key_fn(key_fn, label: str, *, n_devices: int = 4) -> list[Finding]:
    """Probe a layout cache-key function for the PR 5 bug class.

    A sound key treats dtype aliases of one map as one entry (canonical) and
    never lets two *different* maps collide (no ``tobytes()`` aliasing).
    ``structs.mesh_layout_key`` passes; the pre-PR 5 raw-``tobytes`` key
    fails both probes.
    """
    findings = []
    base = (np.arange(6) % n_devices).astype(np.int64)
    if key_fn(base.astype(np.int32), n_devices) != key_fn(base, n_devices):
        findings.append(Finding(
            "JX04", label,
            "cache key is dtype-sensitive: the same device map keyed as "
            "int32 vs int64 misses the cache and re-uploads/re-jits",
        ))
    # m16 shares m32's raw little-endian buffer byte-for-byte while being a
    # different map (4 partitions vs 2) -- the raw-bytes aliasing probe
    m32 = np.asarray([0, 1], dtype=np.int32)
    m16 = np.asarray([0, 0, 1, 0], dtype=np.int16)
    if key_fn(m32, n_devices) == key_fn(m16, n_devices):
        findings.append(Finding(
            "JX04", label,
            "two different device maps alias one cache key (raw-bytes "
            "keying): a re-layout would serve a stale layout",
        ))
    m_2d = m32.reshape(1, 2)
    if key_fn(m32, n_devices) == key_fn(m_2d, n_devices) and m_2d.shape != m32.shape:
        findings.append(Finding(
            "JX04", label,
            "cache key ignores the device map's shape",
        ))
    return findings


def audit_recompile_budget(
    pg,
    program=None,
    *,
    backend: str = "xla",
    d_n: int = AUDIT_MESH_WIDTH,
    windows: tuple = (1, 4, 8, 4, 1),
    rotations: tuple = (0, 1, 0, 1),
    mirror_degrees: tuple = (None,),
    label: str | None = None,
) -> list[Finding]:
    """Scripted relayout/window sweep: distinct jit cache keys must stay
    within the PR 5 cache policy.

    Rotating the partition->device map (an elastic replan) and sweeping the
    window length, revisits included, the number of distinct
    ``window_cache_key``s must not exceed ``DEFAULT_WINDOW_CACHE_SIZE`` --
    and must factor as (distinct window lengths) x (distinct layout
    shapes), i.e. revisiting a placement or a window length never re-jits.
    ``mirror_degrees`` extends the sweep over the hub-mirroring knob:
    every (placement, degree) pair must mint exactly one layout key
    (revisiting a degree never re-jits either).
    """
    from repro.graph.mesh_exchange import DEFAULT_WINDOW_CACHE_SIZE
    from repro.graph.program import SsspProgram

    program = validate_program(program or SsspProgram())
    label = label or f"budget/{program.name}/{backend}/d{d_n}"
    findings = check_cache_key_fn(mesh_layout_key, label, n_devices=d_n)

    base = contiguous_device_map(pg.n_parts, d_n)
    maps = [np.roll(base, r) for r in rotations]
    degrees = [None if md is None else int(md) for md in mirror_degrees]
    layout_keys, window_keys, shape_keys = set(), set(), set()
    for dmap in maps:
        for md in degrees:
            ml = mesh_edge_layout(pg, dmap, d_n, mirror_degree=md)
            layout_keys.add(ml.layout_key)
            _, statics = build_window_consts(pg, program, ml, backend=backend)
            for k in windows:
                key = window_cache_key(ml, k, backend, statics)
                window_keys.add(key)
                shape_keys.add(key[1:])

    n_maps = len({mesh_layout_key(m, d_n) for m in maps})
    n_layouts = n_maps * len(set(degrees))
    if len(layout_keys) != n_layouts:
        findings.append(Finding(
            "JX04", label,
            f"{n_maps} distinct placements x {len(set(degrees))} mirror "
            f"degrees produced {len(layout_keys)} layout keys",
        ))
    if n_layouts > _LAYOUT_CACHE_MAX:
        findings.append(Finding(
            "JX04", label,
            f"sweep visits {n_layouts} layouts > layout cache bound "
            f"{_LAYOUT_CACHE_MAX}",
        ))
    budget = len(set(windows)) * len(shape_keys)
    if len(window_keys) > budget:
        findings.append(Finding(
            "JX04", label,
            f"{len(window_keys)} distinct window jit keys > "
            f"{len(set(windows))} window lengths x {len(shape_keys)} layout "
            "shapes: revisiting a placement or window length re-jits",
        ))
    if len(window_keys) > DEFAULT_WINDOW_CACHE_SIZE:
        findings.append(Finding(
            "JX04", label,
            f"{len(window_keys)} distinct window jit keys exceed the "
            f"window-cache budget {DEFAULT_WINDOW_CACHE_SIZE}: the LRU "
            "would thrash within one replan cycle",
        ))
    return findings


def _layout_mismatch_fields(a, b) -> list:
    """Field names where two ``MeshEdgeLayout``s are not byte-identical."""
    import dataclasses

    bad = []
    for f in dataclasses.fields(type(a)):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            same = (
                isinstance(va, np.ndarray)
                and isinstance(vb, np.ndarray)
                and va.dtype == vb.dtype
                and va.shape == vb.shape
                and np.array_equal(va, vb)
            )
        else:
            same = va == vb
        if not same:
            bad.append(f.name)
    return bad


def audit_delta_cycle(
    pg=None, *, d_n: int = AUDIT_MESH_WIDTH, label: str | None = None
) -> list[Finding]:
    """JX04 over the streaming-mutation path: mutate -> merge -> mutate.

    Drives two delta generations through ``merged_mesh_layout`` and checks
    the cache discipline end to end: every generation mints a *distinct*
    ``layout_key`` (no stale-layout cache hit is reachable), the merged
    layout is byte-identical to a from-scratch build of the mutated graph,
    the merge primes the new graph's layout cache (the next engine adopts it
    instead of rebuilding), and ``window_cache_key`` stays generation-free --
    a merge whose padded shard shapes are unchanged re-jits NOTHING.

    Cycle 1 deletes an existing singleton edge and re-inserts it (content
    churn, shapes provably stable -- the no-re-jit probe); cycle 2 inserts a
    genuinely new edge (shapes may legitimately grow).
    """
    from repro.graph.deltas import (
        EdgeDeltaBuffer,
        apply_delta_buffer,
        merged_mesh_layout,
    )
    from repro.graph.program import SsspProgram

    pg = pg if pg is not None else default_audit_graph()
    label = label or f"budget/delta-cycle/xla/d{d_n}"
    program = validate_program(SsspProgram())
    findings: list[Finding] = []

    dmap = contiguous_device_map(pg.n_parts, d_n)
    layout = mesh_edge_layout(pg, dmap, d_n)
    _, statics = build_window_consts(pg, program, layout, backend="xla")
    keys_seen = {layout.layout_key}
    win_key0 = window_cache_key(layout, 8, "xla", statics)

    g = pg.graph
    n = g.n_vertices
    g_key = g.src.astype(np.int64) * n + g.dst
    uniq, counts = np.unique(g_key, return_counts=True)
    singles = uniq[counts == 1]
    e = int(np.flatnonzero(g_key == singles[0])[0])

    churn = EdgeDeltaBuffer()
    churn.delete(int(g.src[e]), int(g.dst[e]))
    churn.insert(int(g.src[e]), int(g.dst[e]), float(g.weights[e]))
    grow = EdgeDeltaBuffer()
    grow.insert(int(singles[-1] // n), int(singles[-1] % n), 1.25)

    cur = pg
    for cycle, buf in enumerate((churn, grow)):
        new_pg = apply_delta_buffer(cur, buf)
        merged = merged_mesh_layout(cur, new_pg, layout)
        if merged.layout_key in keys_seen:
            findings.append(Finding(
                "JX04", label,
                f"cycle {cycle}: merged layout_key collides with an earlier "
                "generation -- a mutate->merge->mutate cycle can serve a "
                "stale layout under identical shapes",
            ))
        keys_seen.add(merged.layout_key)
        if mesh_edge_layout(new_pg, dmap, d_n) is not merged:
            findings.append(Finding(
                "JX04", label,
                f"cycle {cycle}: the merge did not prime the mutated "
                "graph's layout cache -- the next engine rebuilds from "
                "scratch",
            ))
        scratch = mesh_edge_layout(apply_delta_buffer(cur, buf), dmap, d_n)
        bad = _layout_mismatch_fields(merged, scratch)
        if bad:
            findings.append(Finding(
                "JX04", label,
                f"cycle {cycle}: merged layout differs from a from-scratch "
                f"build of the mutated graph in fields {bad}",
            ))
        _, new_statics = build_window_consts(
            new_pg, program, merged, backend="xla"
        )
        new_key = window_cache_key(merged, 8, "xla", new_statics)
        shapes_same = (
            merged.n_pad == layout.n_pad
            and merged.e_local_pad == layout.e_local_pad
            and merged.e_remote_pad == layout.e_remote_pad
            and merged.w_pad == layout.w_pad
            and merged.m_pad == layout.m_pad
        )
        if shapes_same and new_key != win_key0:
            findings.append(Finding(
                "JX04", label,
                f"cycle {cycle}: padded shapes are unchanged but the window "
                "jit key moved -- every merge would re-jit the window "
                "program",
            ))
        cur, layout, win_key0 = new_pg, merged, new_key
    return findings


# -- the audit matrix ---------------------------------------------------------


def audit_dense(pg, program, backend: str) -> list[Finding]:
    """Trace + audit one dense engine window."""
    from repro.graph.config import EngineConfig
    from repro.graph.traversal import TraversalEngine

    label = f"dense/{program.name}/{backend}"
    engine = TraversalEngine(
        pg, program=program, config=EngineConfig(backend=backend)
    )
    closed = engine.window_jaxpr()
    findings = check_hot_path(closed, label)
    findings += check_pallas_grids(closed, label, expect_kernel=backend != "xla")
    findings += check_identity(program, label)
    findings += check_scopes(closed, label)
    return findings


def audit_mesh(
    pg,
    program,
    backend: str,
    d_n: int = AUDIT_MESH_WIDTH,
    mirror_degree: int | None = None,
) -> list[Finding]:
    """Trace + audit one mesh window over an abstract D-device mesh.

    ``mirror_degree`` audits the hub-mirroring variant: the trace is built
    over the mirrored layout and checked against the mirrored collective
    signature iff that layout actually has hubs (the degenerate zero-hub
    layout must trace -- and audit -- exactly like the unmirrored one).
    """
    tag = "" if mirror_degree is None else f"/mirror{int(mirror_degree)}"
    label = f"mesh/{program.name}/{backend}/d{d_n}{tag}"
    closed = abstract_window_jaxpr(
        pg, program, d_n=d_n, backend=backend, mirror_degree=mirror_degree
    )
    ml = mesh_edge_layout(
        pg, contiguous_device_map(pg.n_parts, d_n), d_n,
        mirror_degree=mirror_degree,
    )
    findings = check_hot_path(closed, label)
    findings += check_pallas_grids(closed, label, expect_kernel=backend != "xla")
    findings += check_mesh_trace(closed, program, label, mirrored=ml.m_pad > 0)
    findings += check_scopes(closed, label)
    return findings


def default_audit_graph():
    """Small weighted power-law graph with a ragged partition: big enough
    that padded shard shapes differ per device, small enough to trace in
    seconds."""
    from repro.graph.generators import rmat_graph, weighted
    from repro.graph.partition import bfs_grow_partition

    g = weighted(rmat_graph(6, 4, seed=7), seed=3)
    return bfs_grow_partition(g, 5, seed=0)


def audit_tree(pg=None, *, backends=AUDIT_BACKENDS, d_n: int = AUDIT_MESH_WIDTH) -> list[Finding]:
    """The full matrix: every builtin program x backend x {dense, mesh},
    the mirrored mesh trace per program (hub threshold
    ``AUDIT_MIRROR_DEGREE``, xla, plus one kernel-backend trace), plus the
    recompile-budget sweep per program and one sweep over the mirror knob."""
    pg = pg if pg is not None else default_audit_graph()
    findings = []
    for ctor in BUILTIN_PROGRAMS.values():
        program = ctor()
        for backend in backends:
            findings += audit_dense(pg, program, backend)
            findings += audit_mesh(pg, program, backend, d_n)
        findings += audit_mesh(
            pg, program, "xla", d_n, mirror_degree=AUDIT_MIRROR_DEGREE
        )
        findings += audit_recompile_budget(pg, program, backend="xla", d_n=d_n)
    findings += audit_recompile_budget(pg, None, backend="pallas-interpret", d_n=d_n)
    findings += audit_mesh(
        pg, BUILTIN_PROGRAMS["sssp"](), "pallas-interpret", d_n,
        mirror_degree=AUDIT_MIRROR_DEGREE,
    )
    findings += audit_recompile_budget(
        pg, None, backend="xla", d_n=d_n, windows=(1, 8, 1),
        mirror_degrees=(None, AUDIT_MIRROR_DEGREE, None),
        label=f"budget/mirror-sweep/xla/d{d_n}",
    )
    findings += audit_delta_cycle(pg, d_n=d_n)
    return findings
