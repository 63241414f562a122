"""JAX's persistent compilation cache, placed by the entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`use_compile_cache` before they compile anything; importing this
module (or any other) sets nothing.
"""
from __future__ import annotations

import os

import jax


def use_compile_cache(default_dir) -> str:
    """Keep compiled programs across runs; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here.  Otherwise the cache goes to
    ``default_dir``, which callers keep at one fixed path inside the
    checkout: the path is part of what a later run must find again.
    Every program is cached, however quickly it compiled.  Call this
    before the first compile: JAX opens its cache once per process.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.fspath(default_dir)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
