"""Structured findings: the one record type both analysis layers emit.

A finding names a rule (``RULES``), the artifact it fired on (a ``file:line``
for AST lint, a trace label like ``mesh/bfs/pallas-interpret`` for the jaxpr
auditor), and a human message.  ``python -m repro.analysis`` renders findings
one per line and exits non-zero iff any exist, which is what makes the layer
CI-gateable.
"""

from __future__ import annotations

import dataclasses

#: rule id -> one-line invariant (mirrored in ROADMAP "Static guarantees")
RULES = {
    # layer 1: jaxpr auditor (trace-level, per program x backend x engine)
    "JX01": "no host callbacks / transfers / debug prints on the superstep hot path",
    "JX02": "SPMD collectives balanced: parts-axis only, count/order per the "
    "program's declared collective_signature(), globally-synced loop conds",
    "JX03": "every Pallas grid dimension provably >= 1; kernel backend "
    "actually lowers to pallas_call",
    "JX04": "layout/jit cache keys canonical (no dtype/shape-blind aliasing); "
    "relayout/window sweeps stay within the window-cache budget",
    "JX05": "reduction identity is the program's dtype-derived identity and "
    "is a fixed point of relax/combine",
    "JX06": "every window operation sits under one plane scope and one role "
    "scope (repro.spans)",
    # layer 2: AST lint (source-level, repo-specific)
    "AL01": "no np. / .item() / float() / Python branches on traced values "
    "inside registered traced functions",
    "AL02": "no unbounded long-lived dict caches (BoundedCache LRU + coerced "
    "keys required)",
    "AL03": "Pallas kernels base-initialize their output tile on the first "
    "grid step",
    "AL04": "no tobytes()-style cache keys without shape/dtype context",
    "AL05": "no unused module-level imports",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at one location."""

    rule: str  # key into RULES
    where: str  # "path/to/file.py:LINE" or an audit trace label
    message: str  # what exactly is wrong, with the offending symbol

    def __post_init__(self):
        assert self.rule in RULES, f"unknown rule id {self.rule!r}"

    def __str__(self) -> str:
        return f"{self.where}: {self.rule} {self.message}"


def render(findings: list[Finding]) -> str:
    """One line per finding, stable order (by rule, then location)."""
    ordered = sorted(findings, key=lambda f: (f.rule, f.where, f.message))
    return "\n".join(str(f) for f in ordered)
