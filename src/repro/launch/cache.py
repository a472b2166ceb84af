"""JAX's persistent compile cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``benchmarks/``, ``examples/``) calls
:func:`enable_compile_cache` once before it compiles anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set.  Otherwise the cache sits at the fixed path ``<checkout>/.jax_cache``
(git-ignored): a path built from a temporary name, a pid or the time would
never be hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fallback cache directory: ``.jax_cache`` at the root of the checkout.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compile cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR


def cache_entries(path: Path) -> int:
    """Number of entries in a cache directory (0 before the first)."""
    return sum(1 for _ in path.iterdir()) if path.is_dir() else 0
