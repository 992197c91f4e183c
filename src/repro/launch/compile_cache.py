"""Where the launchers keep JAX's persistent compilation cache.

Call :func:`use_compile_cache` before JAX starts.  A cache directory the
machine names in ``JAX_COMPILATION_CACHE_DIR`` is kept; otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout — a fixed path, since
the path is part of the cache key.  Importing ``repro`` never turns the
cache on: compiles for a described chip in the tests would warn on it.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(REPO_ROOT / ".jax_cache"))
