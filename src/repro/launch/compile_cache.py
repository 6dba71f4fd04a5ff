"""JAX's persistent compilation cache for this repo's entry points.

``enable_compile_cache()`` is called from the ``main`` of each entry point
(``repro.launch.serve``, ``chip_smoke.py``, ``benchmarks/run.py``), never at
import, so tests and library users keep JAX's own default (off):

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and uses
  that directory; nothing else is set;
* otherwise the cache lives at ``<repo>/.jax_cache`` — a fixed path, since
  the directory is part of what a later process must find again (git
  ignores it).
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
