"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives at one fixed
directory inside the checkout, ``.cache/jax`` (git-ignored) — fixed
because the path is part of what makes a later run find the entries.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]

#: the checkout's git-ignored cache root (compile cache, smoke stores)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT_CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
