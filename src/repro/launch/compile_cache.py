"""Persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py``
before their first compile, never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself and
nothing here overrides it; otherwise the cache lives at one fixed,
git-ignored path inside the checkout.  The path is fixed because JAX
finds an entry again only in the directory it wrote it to.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
