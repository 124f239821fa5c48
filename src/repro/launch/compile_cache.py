"""Persistent compilation cache for the entry-point scripts.

Call `enable_compile_cache()` first thing in a script's `__main__` block
(`chip_smoke.py`, the benchmarks) — never from a library module, so that
importing `repro` changes no JAX configuration.

Where the cache lives:
  * `JAX_COMPILATION_CACHE_DIR`, when it is set — and no other directory;
  * otherwise `<checkout>/.jax_cache` (gitignored). The path is part of
    what a later run must find again, so it is fixed: no temp name, PID
    or timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its one directory."""
    path = Path(os.environ.get(ENV_VAR) or DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
