"""Where JAX's persistent compilation cache lives.

The path is part of what the cache is keyed on, so it must not move
between runs: a temp name, a pid or a timestamp would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's compilation cache at a fixed directory and return it.

    A `JAX_COMPILATION_CACHE_DIR` set from outside wins and is left to
    JAX itself; otherwise the cache goes to `.jax_cache` at the root of
    this checkout. Entry points call this before their first compile;
    tests never do."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
