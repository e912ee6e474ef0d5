"""JAX's persistent compilation cache, pointed at one fixed directory.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``,
``repro.launch``) call :func:`enable_compile_cache` before their first
compile; nothing calls it at import.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, no directory is
  set here;
* unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is
  fixed because it is part of every cache key: a directory that moves never
  hits.

Either way every compile is cached, however short.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    # the Pallas kernels compile in well under JAX's default 1 s floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
