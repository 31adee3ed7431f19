"""JAX's persistent compilation cache for the entry points that run on the
chip (``chip_smoke.py``, ``repro.launch.serve``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache lives at a fixed directory inside the
checkout, ``<repo>/.jax_cache``: the path is part of the cache key, so it
never depends on a temp name, a pid or the time. Called by entry points,
never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


class CompileStats:
    """Counts persistent-cache hits and misses and sums backend compile
    seconds, from JAX's own monitoring events."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def enable_compile_cache() -> tuple:
    """Turn the persistent cache on; returns (cache dir, CompileStats)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compile: the fused step's small buckets compile in
    # under JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    stats = CompileStats()
    jax.monitoring.register_event_listener(stats._event)
    jax.monitoring.register_event_duration_secs_listener(stats._duration)
    return path, stats
