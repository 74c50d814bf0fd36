"""The repo's one persistent XLA compile cache.

Every entry point that compiles (the tests, ``chip_smoke.py``,
``benchmarks.run`` and the examples) calls :func:`use_compile_cache`
before its first compile, so compiled programs are found again by the
next process at one fixed path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs across processes.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set (an empty value turns the
    cache off), JAX reads it itself and this sets nothing.  Otherwise
    the cache goes to ``<repo>/.jax_cache``.
    """
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
