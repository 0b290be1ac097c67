"""JAX's persistent compilation cache for the serving entry points.

The cache key includes the cache directory, so the directory must not
move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, and nothing here overrides it) or
one fixed directory inside the checkout, ``<checkout>/.jax_cache``
(listed in ``.gitignore``). The path is never built from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call at start-up, before the first compilation."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
