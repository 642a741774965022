"""Persistent JAX compilation cache for the repository's entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`use_compile_cache`
before their first compile; no library module calls it, so importing
``repro`` never changes a caller's JAX configuration.
"""
from __future__ import annotations

import os


def use_compile_cache(root: str) -> str:
    """Keep compiled programs across runs and return the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is configured; otherwise the cache lives in the
    fixed ``<root>/.jax_cache`` (a fixed path, because the path is part of
    each entry's key).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
