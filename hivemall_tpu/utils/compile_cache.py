"""Persistent XLA compile cache, placed from outside the program.

Every entry point calls :func:`enable_compile_cache` before its first jit
(CLI, chip_smoke, the benchmark, fleet worker, retrain child, bulk
workers). Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
and nothing is set in code; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (git-ignored). The path is part of the cache
key's environment, so it never derives from tempfile, pid or time — a
directory that moves never hits.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

__all__ = ["DEFAULT_DIR", "enable_compile_cache", "cache_stats"]

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_lock = threading.Lock()
_counts: Dict[str, int] = {_HIT: 0, _MISS: 0}
_listening = False


def _on_event(event: str, **kw) -> None:
    if event in _counts:
        with _lock:
            _counts[event] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    force. Idempotent."""
    global _listening
    import jax
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses seen by this process since
    :func:`enable_compile_cache` (jax.monitoring ground truth)."""
    with _lock:
        return {"hits": _counts[_HIT], "misses": _counts[_MISS]}
