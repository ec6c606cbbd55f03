"""JAX's persistent compilation cache, and a log of compile time per program.

Entry points call ``enable_compile_cache()`` before their first compile;
importing the library never touches the cache.  Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and this module sets no
other directory.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import collections
import os

import jax
from jax import monitoring

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache small programs too: an autoscaled instance's first steps
    # compile dozens of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compile seconds per program name, and persistent-cache hits,
    from JAX's monitoring events (a cache hit is counted as its load time)."""

    def __init__(self):
        self.seconds: dict[str, float] = collections.defaultdict(float)
        self.count: dict[str, int] = collections.defaultdict(int)
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw):
        if event == _COMPILE_EVENT:
            name = str(kw.get("fun_name", "?"))
            self.seconds[name] += duration
            self.count[name] += 1

    def _on_event(self, event: str, **kw):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def total(self) -> float:
        return sum(self.seconds.values())

    def top(self, n: int = 8) -> list[tuple[str, float, int]]:
        rows = sorted(self.seconds.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v, self.count[k]) for k, v in rows]
