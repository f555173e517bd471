"""JAX's persistent compilation cache, placeable from outside.

The command-line entry points (`python -m repro.launch.train`,
`python -m repro.launch.serve`) and `chip_smoke.py` call
`enable_compile_cache()` before their first compile; nothing calls it at
import.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and this module sets no other directory.  Otherwise the cache lives at
the fixed `<checkout>/.jax_cache` (git-ignored): the directory is part
of what a later run must find again, so it is never built from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root, three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """Where compiled programs are cached; touches no JAX backend."""
    return os.environ.get(ENV_VAR) or os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on at `compile_cache_dir()` and return
    that directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
