"""Persistent XLA compilation cache, placed from outside the program.

``use_compile_cache()`` is called once at start-up by the scripts that drive
the system (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``), never
at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself
and this sets no other directory. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of what a cache entry is found by, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
