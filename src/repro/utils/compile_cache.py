"""Where this checkout keeps what it builds at run time.

``CACHE_DIR``, the checkout's ``.cache/`` (gitignored), holds the trained
GBDT models and JAX's persistent compilation cache. The compile cache's
path is part of its key, so it is one fixed directory: never a temporary
name.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing else is set here. Otherwise the cache goes to
    ``CACHE_DIR/jax``. Call it from an entry point before the first
    compile, never at import (jax stays a soft dependency of the library).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CACHE_DIR / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
