"""The program's trace vocabulary: host spans and device scopes.

Host spans.  ``span(name)`` marks a stretch of host work (``graph.init``,
``graph.partition.grow``, ...).  It always enters a
``jax.profiler.TraceAnnotation``, which costs next to nothing while no
profiler runs; under a profiler the span lands in the same trace, on the
same clock, as the device ops, so an idle stretch of the device can be put
down to what the host was doing.  Inside a ``collect()`` block each span
also appends ``(name, start, end)`` from ``time.perf_counter`` to the
block's list.  Names are dotted: ``graph.layout.local`` nests in
``graph.layout``.

Device scopes.  ``scope(plane, role)`` wraps traced operations of the
window program in ``jax.named_scope``s.  The names reach each op's HLO
``op_name`` metadata, so device time can be put down to a plane and a
role:

* planes: ``local`` (the closure loop, or the stationary local pass) and
  ``remote`` (the superstep-boundary exchange);
* roles: ``gather`` (the candidate gathers and masks), ``relax`` (the
  segment reduce and combine, or the relax kernel), ``counters`` (the work
  counters and their writes), ``frontier`` (frontier, superstep budget and
  loop conditions) and ``collective`` (mesh collectives);
* ``finish``: the window's epilogue, with no plane.

A ``collective`` scope may nest inside another role (the ``pmax`` that
syncs a frontier test); it then names the op.  ``scope_of`` reads the
(plane, role) back from a name stack; rule JX06 of the jaxpr auditor holds
every op of the window to it.

jax is imported where it is used: the partitioner, which the serving and
analysis layers import without a device runtime, opens spans too.
"""

from __future__ import annotations

import contextlib
import contextvars
import time

PLANES = ("local", "remote")
ROLES = ("gather", "relax", "counters", "frontier", "collective")
COLLECTIVE = "collective"
FINISH = "finish"
_VOCABULARY = frozenset(PLANES + ROLES + (FINISH,))

#: the innermost ``collect()`` block's list, per thread and task
_SINK: contextvars.ContextVar = contextvars.ContextVar("spans_sink", default=None)


@contextlib.contextmanager
def span(name: str):
    """A host span: a profiler annotation, and a record inside ``collect()``."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        sink = _SINK.get()
        if sink is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            sink.append((name, t0, time.perf_counter()))


@contextlib.contextmanager
def collect():
    """Record the spans that end inside the block: yields the list of
    ``(name, start, end)`` they are appended to, in the order they end."""
    records = []
    token = _SINK.set(records)
    try:
        yield records
    finally:
        _SINK.reset(token)


def span_seconds(records) -> dict:
    """``{name: seconds}`` of ``collect()`` records, summed by name, in the
    order each name first started."""
    out = {}
    for name, t0, t1 in sorted(records, key=lambda r: r[1]):
        out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


@contextlib.contextmanager
def scope(plane: str, role: str | None = None):
    """Device scope ``plane/role`` (``scope(FINISH)`` for the epilogue)."""
    import jax

    with jax.named_scope(plane):
        if role is None:
            yield
        else:
            with jax.named_scope(role):
                yield


def collective():
    """The scope of a mesh collective, inside the role it serves."""
    import jax

    return jax.named_scope(COLLECTIVE)


def scope_of(names) -> tuple[str, str] | None:
    """``(plane, role)`` of an op whose name stack holds ``names``,
    outermost first (``("finish", "finish")`` in the epilogue), or None
    where the op sits outside the vocabulary's one plane and one role.
    Names outside the vocabulary (``while``, ``body``, ``vmap()``, ...)
    are passed over."""
    found = [n for n in names if n in _VOCABULARY]
    if found[:1] == [FINISH]:
        plane, role, rest = FINISH, FINISH, found[1:]
    elif len(found) >= 2 and found[0] in PLANES and found[1] in ROLES:
        plane, role, rest = found[0], found[1], found[2:]
    else:
        return None
    if not rest:
        return plane, role
    if rest == [COLLECTIVE] and role != COLLECTIVE:
        return plane, COLLECTIVE
    return None
