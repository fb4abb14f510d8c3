"""Counts XLA compilations from JAX's own monitoring events.

`compiles` counts the programs JAX had to obtain an executable for (an
in-memory cache hit emits nothing), `cache_hits` those of them that the
persistent compilation cache supplied; the difference was compiled."""
from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
