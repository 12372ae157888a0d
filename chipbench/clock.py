"""Compile clock, from JAX's own monitoring events: seconds spent
building programs, how many were built (compiled, or read back from the
persistent cache: JAX times both as one backend compile), and how many of
those were persistent-cache hits."""
from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _duration(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return False

    def lap(self) -> tuple:
        """(seconds, programs built, cache hits) so far."""
        return self.seconds, self.compiles, self.cache_hits
