"""Where the program keeps what it caches between runs: inside the
checkout, at fixed paths (both are listed in ``.gitignore``).

  * JAX's persistent compilation cache — the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads that
    variable itself), else ``<repo>/.jax_cache``.  The path is part of
    the cache's key, so it never depends on a temp name, a pid or the
    time.
  * The dispatch autotune cache — ``REPRO_AUTOTUNE_CACHE`` when set,
    else ``<repo>/.autotune.json`` (read by ``kernels.dispatch``).

:func:`configure_caches` is called by the entry points (``chip_smoke.py``,
``launch.serve``, ``benchmarks.run``), never on import and never by tests.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"
AUTOTUNE_CACHE = REPO_ROOT / ".autotune.json"


def autotune_cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or str(AUTOTUNE_CACHE)


def configure_caches() -> str:
    """Turn on JAX's persistent compilation cache at its fixed path;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
