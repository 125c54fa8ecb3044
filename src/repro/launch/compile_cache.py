"""Where JAX keeps its persistent compilation cache for this repo's entry
points.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads it
at start-up and this module sets nothing.  Otherwise the cache goes to one
fixed directory inside the checkout (``.jax_cache/`` beside ``src/``,
listed in ``.gitignore``).  The path is part of the cache key, so it is
never built from a temporary name, a process id or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call it from an entry point's ``main``, before the
    first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
