"""Where the entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache sits at a fixed ``<checkout>/.jax_cache``
(git-ignored): the path is part of the cache's key, so a directory that
moved between runs would never hit.  Only entry points call this — the
serve CLI, ``chip_smoke.py`` and ``benchmarks/run.py`` — never a library
import.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]     # <checkout>/src/repro/


def use_checkout_cache() -> str:
    """Point the persistent compilation cache at ``<checkout>/.jax_cache``
    unless the environment already names one; returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
