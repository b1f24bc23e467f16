"""JAX's persistent compilation cache for the program's entry points.

Every entry point (``chip_smoke.py``, ``python -m repro.session``,
``repro.launch.serve``, ``repro.launch.train``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` before its first compile, so a second
run of the same program loads its executables instead of compiling them
again.  Importing the library sets nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and
no other directory is set.  Otherwise the cache lives in ``.jax_cache`` at
the root of the checkout: a fixed path, because the directory is part of
what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, when that is unset, at ``<checkout>/.jax_cache``; returns the
    directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
