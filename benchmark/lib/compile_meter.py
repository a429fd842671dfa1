"""Count what JAX builds, from its own monitoring events: every
executable it compiles or fetches from the persistent cache fires
``backend_compile_duration``.  The window must see none."""
from __future__ import annotations

import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.executables = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.executables += 1

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
