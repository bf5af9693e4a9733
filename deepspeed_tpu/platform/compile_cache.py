"""Where JAX's persistent compilation cache lives.

The cache is placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this module sets nothing in code. Otherwise the cache
goes to one fixed directory under the checkout — the path is part of the
cache's key, so a temp name, a pid or a time would never hit.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache(default_dir: Optional[str] = None) -> str:
    """Call before the first compile; returns the directory in effect."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = default_dir or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
