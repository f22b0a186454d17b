"""Where the entry points keep JAX's persistent compilation cache.

Called by the launchers' ``main`` and by ``chip_smoke.py`` only — library
code and tests never place a cache. The directory is fixed: a cache is
found again only at the path it was written to, so a temp, pid- or
time-derived directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep the cache where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads
    the variable itself), else in ``<checkout>/.jax_cache``. Returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
