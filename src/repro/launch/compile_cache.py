"""Where JAX's persistent compilation cache lives.

A cold start compiles every round program again; the persistent cache
lets a later process on the same machine load them instead. Entry
points (the train CLI, the cohort benchmark, chip_smoke.py) call
``enable_compile_cache`` before their first compile. Nothing calls it
at import, so importing the package never changes JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, so every run from this checkout
# finds the entries its predecessors wrote
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into its config; that setting is left alone. Otherwise the cache goes
    to ``<checkout>/.jax_cache``. Takes effect only before the process's
    first compile (JAX decides once whether the cache is in use)."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
