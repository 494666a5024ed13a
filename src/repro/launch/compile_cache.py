"""Where JAX keeps its persistent compilation cache.

A cache is found again only at the same path, so the entry points
(``launch.train``, ``benchmarks/run.py``, ``chip_smoke.py``) keep it at one
fixed place: the directory ``JAX_COMPILATION_CACHE_DIR`` names, which JAX
reads by itself, or else ``<repo>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
