"""Persistent compilation cache at a fixed path.

Entry points call :func:`use_compile_cache` from their ``main`` (never at
import).  JAX's own ``JAX_COMPILATION_CACHE_DIR`` wins when it is set;
otherwise compiled programs are kept in ``<checkout>/.jax_cache`` — one
fixed directory, so a later run finds what an earlier run wrote (a temp,
pid- or time-derived directory would never hit).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(_ENV)
    if path:
        return path     # JAX reads the variable itself: set nothing else
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
