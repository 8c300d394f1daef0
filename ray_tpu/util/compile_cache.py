"""Where compiled programs are kept between processes.

One rule, applied first by every entry that owns a chip (chip_smoke.py's
phases, bench.py, LLMServerImpl, TrainStepBundle): the
persistent XLA compile cache lives where `JAX_COMPILATION_CACHE_DIR`
says — then no directory is set in code, jax reads the variable itself —
and otherwise at `<checkout>/.jax_cache`. The path is part of what makes a
cache useful across runs, so it is never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from typing import Dict

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Place the compile cache; returns the directory in use."""
    import jax

    # keep every program, not only those over jax's one-second
    # default: otherwise a warm run still compiles the quick ones, and
    # the borderline ones trickle into the cache run after run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileWatch:
    """Counts what this process compiles, from jax's own monitoring
    events: programs handed to the backend compiler, seconds spent
    there (a cache hit costs its retrieval), persistent-cache hits and
    writes. Listeners cannot be removed one by one, so make one watch
    per process."""

    def __init__(self) -> None:
        from jax import monitoring
        self.programs = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1      # recorded where an entry is written

    def snapshot(self) -> Dict[str, float]:
        return {"programs": self.programs,
                "compile_s": round(self.compile_s, 2),
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}
