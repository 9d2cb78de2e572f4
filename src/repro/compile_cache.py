"""JAX persistent compilation cache placement for the entry points.

``enable_compile_cache()`` is called from the ``main`` of each entry point
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``), never at
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is changed here; otherwise the cache lives at one fixed path in the
checkout, ``<repo>/.jax_cache`` (gitignored).  The path is part of every
cache key, so it is never derived from a temporary name, a process id or
the time: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
