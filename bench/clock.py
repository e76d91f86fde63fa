"""Compile time and compile counts of this process, from ``jax.monitoring``.

Splits set-up into compilation and the rest, and counts compiles inside
the measured window, where there should be none: a backend compile, or a
program loaded from the persistent cache, both mean a shape the warm-up
did not cover.
"""

from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileClock:
    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == HIT_EVENT:
            self.cache_hits += 1
        elif event == MISS_EVENT:
            self.cache_misses += 1

    def programs(self) -> int:
        """Programs compiled or loaded from the persistent cache so far."""
        return self.compiles + self.cache_hits
