"""Where the process keeps JAX's persistent compilation cache.

``enable()`` is called once at the start of every driver (``chip_smoke.py``,
the ``repro.launch`` entry points). Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here. Otherwise the cache goes
to ``<checkout>/.jax_cache``: a fixed path, never built from a temp name, a
pid or the time, so a later run from the same checkout finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point the persistent compilation cache at its directory; returns
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
