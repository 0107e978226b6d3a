"""Counts XLA compilations and persistent-cache hits process-wide (copied
from ``chip_smoke.CompileMeter``): set-up reads how much it compiled, and
the window reads the difference of two snapshots, which must be 0."""
from __future__ import annotations


class CompileMeter:
    def __init__(self):
        from jax import monitoring
        self.n = self.hits = 0
        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.n, self.secs, self.hits
