"""Where an entry point keeps JAX's persistent compilation cache.

Only entry points call :func:`enable` (``chip_smoke.py`` and the
``__main__`` of ``launch/serve.py`` / ``launch/train.py``); library
modules and tests leave JAX's cache settings alone.  A directory named
in ``$JAX_COMPILATION_CACHE_DIR`` wins — JAX reads that variable itself,
so nothing is set then.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (git-ignored): the path is part of the cache key,
so a moving directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
