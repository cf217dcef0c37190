"""Where JAX keeps its persistent compilation cache.

A cache entry is found again only from the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` where the environment
sets it (JAX reads the variable itself), else ``.jax_cache`` at the root
of this checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. Call once at the start of an entry point,
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
