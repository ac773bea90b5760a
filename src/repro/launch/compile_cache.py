"""JAX's persistent compilation cache for the launchers and chip_smoke.py.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
module sets no directory. Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``), a fixed path, so a later run of the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; return its directory.

    Call before the first compile. Every program is cached, however fast it
    compiled: the served path runs eagerly, and its many small per-op
    programs are most of a cold start."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
