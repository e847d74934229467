"""Where JAX keeps its persistent compilation cache.

A cache is found again only at the same path, so the path never carries a
process id, a time or a temporary name.  ``JAX_COMPILATION_CACHE_DIR``, when
set, is JAX's own setting and wins; otherwise the cache lives in
``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
