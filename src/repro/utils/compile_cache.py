"""Persistent XLA compilation cache for the entry points.

Every script that drives the system (``chip_smoke.py``, ``examples/``,
``benchmarks/``) calls ``enable_compile_cache()`` first thing in its
``__main__`` block, so a second run of the same program on the same
backend skips XLA compilation.  Tests never call it.

Rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives at one fixed path in the
checkout, ``.jax_cache/`` (listed in ``.gitignore``) — never a path made
from a temporary name, a pid or the time, since the directory is part of
what makes a later run find the entries.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Optional[str]:
    """The directory ``enable_compile_cache`` sets, or None when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX then uses it unprompted)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax
    path = compile_cache_dir()
    if path is None:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", path)
    return path
