"""JAX's persistent compilation cache at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the checkout
root: a fixed path, because the path is part of what a cache entry is
found by, so a temporary or per-process directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: str | os.PathLike | None = None) -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  ``root`` defaults to this checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root or CHECKOUT) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
