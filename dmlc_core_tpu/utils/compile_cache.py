"""Where JAX keeps its persistent compilation cache.

Entry points (the chip benchmark's harness, the examples) call
:func:`place_compile_cache` once at start; library code and tests never
do. The path is part of the cache key, so it is fixed: a directory built
from a tempdir, a pid or the time would never hit.
"""

from __future__ import annotations

import os

__all__ = ["place_compile_cache"]

REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Return the cache directory in use. ``JAX_COMPILATION_CACHE_DIR``,
    when set, wins and nothing is set in code (JAX reads it itself);
    otherwise the cache goes to ``<repo>/.jax_cache`` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
