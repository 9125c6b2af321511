"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) calls :func:`enable_compile_cache` once, before the
first compile, so a second run of a 28-layer model reads its programs
back instead of recompiling them.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path: the cache is only found again if the directory stays put
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    this leaves JAX's setting alone; otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
