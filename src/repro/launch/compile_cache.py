"""Where JAX keeps its persistent compilation cache, and how often the
process compiles.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) calls :func:`enable_compile_cache` once, before the
first compile, so a second run of a 28-layer model reads its programs
back instead of recompiling them.

Importing this module also counts compiles: listeners on JAX's
monitoring events add up, by function name, each backend compile (which
includes reading the program back from the persistent cache) and its
seconds, and the persistent cache's hits and misses.
:func:`compile_counts` returns a snapshot; the difference of two
snapshots is what was compiled between them.
"""
from __future__ import annotations

import collections
import os
import threading
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path: the cache is only found again if the directory stays put
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_compiles: collections.Counter = collections.Counter()
_compile_s: collections.Counter = collections.Counter()
_cache: collections.Counter = collections.Counter()


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


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == BACKEND_COMPILE:
        name = str(kw.get("fun_name", "?"))
        with _lock:
            _compiles[name] += 1
            _compile_s[name] += seconds


def _on_event(event: str, **kw) -> None:
    if event in (CACHE_HITS, CACHE_MISSES):
        with _lock:
            _cache[event] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_counts() -> dict:
    """Compiles and compile seconds by function name since the process
    imported this module, and the persistent cache's hits and misses."""
    with _lock:
        return {"compiles": dict(_compiles), "compile_s": dict(_compile_s),
                "cache_hits": _cache[CACHE_HITS],
                "cache_misses": _cache[CACHE_MISSES]}
