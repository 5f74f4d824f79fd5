"""Where JAX's persistent compilation cache lives: one rule for every
entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, wins, and nothing is set in code
(JAX reads the variable itself).  Otherwise the cache is
``<checkout>/.jax_cache``: a fixed path, never a temporary, per-process
or per-run one, so a second run from the same checkout finds what the
first one compiled.

Entry points call :func:`enable_compile_cache` from their ``main``;
importing a library module never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
