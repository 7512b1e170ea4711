"""Seconds in backend compilation (or in loading a compiled program
from the persistent cache) and how many programs were built, from
jax's own monitoring events. Copied from `chip_smoke.py`'s meter."""

from __future__ import annotations


class CompileMeter:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as monitoring
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += seconds
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return self.seconds, self.programs
