"""Compile counting from JAX's monitoring events (after chip_smoke.py's
CompileClock).

``/jax/core/compile/backend_compile_duration`` fires once per program that
is compiled or loaded from the persistent cache; ``cache_hits`` counts the
loads alone. Listeners cannot be removed, so one clock serves a process.
"""
from __future__ import annotations

import jax

_CLOCK = None


class CompileClock:
    def __init__(self):
        self.programs = 0      # compiled or loaded from the persistent cache
        self.cache_hits = 0
        self.seconds = 0.0     # backend compile time (a load counts its read)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def compile_clock() -> CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK
