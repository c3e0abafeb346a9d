"""JAX's persistent compilation cache, placed from outside or at a fixed path.

The directory is part of each entry's key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX itself reads that variable,
and nothing here overrides it), otherwise ``<checkout>/.jax_cache``, which git
ignores.  The entry points call ``enable_compile_cache`` before their first
compile: ``chip_smoke.py``, ``repro.launch.train`` and ``benchmarks.run``.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: the environment's choice, else the fixed one."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()``; returns it."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
