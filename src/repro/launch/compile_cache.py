"""JAX's persistent compilation cache, placed from outside.

Drivers call :func:`use_compile_cache` at the start of ``main`` (never on
import, and tests never call it).  ``JAX_COMPILATION_CACHE_DIR``, when
set, is read by JAX itself and wins; otherwise the cache lives at the
fixed ``<repo root>/.jax_cache``.  A fixed path matters: a directory
named after a pid, a time or a temporary name is never found again by
the next run.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where the persistent compile cache goes: the environment's choice,
    else ``<repo root>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
