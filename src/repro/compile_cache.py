"""JAX's persistent compilation cache, placed from outside or at one fixed path.

``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache goes: JAX reads
that variable itself, and nothing here sets another directory.  Otherwise the
cache goes to ``.jax_cache`` at the root of the checkout (gitignored).  The
path is part of the cache's key, so it never holds a temporary name, a pid or
the time.  Call ``enable_compile_cache`` once, before the first compile.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
