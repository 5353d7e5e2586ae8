"""Where JAX's persistent compilation cache lives.

The entry point ``chip_smoke.py`` calls :func:`enable_compile_cache` once
at startup; importing a library module never touches the cache.  The
directory is fixed — never a tempdir, PID or timestamp — because only a run
that looks in the same directory finds the programs an earlier run
compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (placed from
    outside — no other directory is set in code), else the fixed
    ``<repo>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
