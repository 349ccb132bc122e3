"""Compilations as JAX reports them (`jax.monitoring`). Copied from
`chip_smoke.py:CompileMeter`, with the time of every backend compile kept
so that the ones inside a window can be counted."""

from __future__ import annotations

import time


class CompileMeter:
    """Persistent-cache hits and misses, and each backend compile's end
    time and seconds. A cache hit still reports a short backend-compile
    event, so `events` holds every program JAX had to get, from the
    compiler or from the cache."""

    def __init__(self):
        self.hits = self.misses = 0
        self.events = []  # (perf_counter at the end, seconds)

    def install(self) -> "CompileMeter":
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration))

    def seconds(self) -> float:
        return sum(d for _, d in self.events)

    def between(self, t0: float, t1: float) -> int:
        """Backend compiles that ended in `[t0, t1]`."""
        return sum(1 for t, _ in self.events if t0 <= t <= t1)
