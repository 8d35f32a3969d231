"""Count the executables JAX builds, to show that none is built in a window."""

from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Executables built (XLA compiles, and loads from the persistent
    cache, which JAX reports under the same event) and persistent-cache
    loads, since the counter was installed."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.built = 0
        self.cache_loads = 0
        self._lock = threading.Lock()
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.built += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self.cache_loads += 1

    def snapshot(self) -> tuple[int, int]:
        with self._lock:
            return self.built, self.cache_loads
