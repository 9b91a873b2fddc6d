"""Counts XLA compilations (cache loads included) through jax.monitoring,
so that a compile inside the measured window makes the run incorrect."""
from __future__ import annotations


class CompileCounter:
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = self.in_window = 0
        self.cache_hits = self.cache_misses = 0
        self.window_open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if event == self.BACKEND_COMPILE:
            self.total += 1
            self.in_window += self.window_open

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
