"""repro.analysis: static guarantees for the traversal engines, CI-gated.

Two layers over one Finding record:

- **jaxpr auditor** (:mod:`repro.analysis.jaxpr_audit`, rules JX01-JX06):
  abstractly traces every builtin :class:`~repro.graph.program.VertexProgram`
  through the dense ``TraversalEngine`` window and the mesh
  ``MeshTraversalProgram`` body (via ``AbstractMesh`` -- zero real devices
  needed) and walks the ClosedJaxpr for host interop on the hot path,
  collective balance against ``collective_signature()``, Pallas grid
  degeneracy, cache-key canonicality / recompile budget, and reduction
  identities.
- **AST lint** (:mod:`repro.analysis.lint`, rules AL01-AL05): repo-specific
  source rules -- traced-function purity, bounded caches, kernel output-tile
  initialization, tobytes cache keys, unused imports.

Run ``python -m repro.analysis`` (full audit + lint, exit 0 iff clean) or
``python -m repro.analysis --fixtures`` (the known-bad corpus in
:mod:`repro.analysis.fixtures` must be 100% flagged).  Both run as blocking
tier-1 CI steps.
"""

from repro.analysis.findings import RULES, Finding, render
from repro.analysis.lint import lint_paths, lint_source

__all__ = [
    "RULES",
    "Finding",
    "audit_tree",
    "lint_paths",
    "lint_source",
    "render",
]


def __getattr__(name):
    # the AST layer must stay importable without jax (the CI lint job
    # installs only ruff); the jaxpr auditor loads on first touch
    if name == "audit_tree":
        from repro.analysis.jaxpr_audit import audit_tree

        return audit_tree
    raise AttributeError(name)
