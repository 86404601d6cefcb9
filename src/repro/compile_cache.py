"""JAX's persistent compilation cache, placed from outside or at a fixed path.

A cold TPU run of the device engine spends much of its time compiling (one
program per pow2 level shape and V-cycle stage), so every entry point —
``python -m repro``, ``chip_smoke.py``, the benchmarks — turns the cache on
before its first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left
to JAX and nothing else is set.  Otherwise the cache lives at one fixed
path inside the checkout, ``<checkout>/.jax_cache`` (listed in
``.gitignore``), so the next run in the same checkout finds what this one
compiled; a directory derived from a temp dir, a pid or the time would
start empty every run.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to.

    Importing jax is left to whoever compiles: before that import the
    variable is enough (JAX reads it at import), after it the live config
    is updated too.  Child processes inherit the same directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = str(DEFAULT_DIR)
    os.environ[ENV_VAR] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
