"""Compilations, persistent-cache hits and compile seconds, from JAX's
monitoring events — a copy of ``chip_smoke.CompileMeter`` (PR 21)."""

from __future__ import annotations

import threading


class CompileMeter:
    """Counts XLA compilations (persistent-cache hits included), cache
    hits and compile seconds (lowering + backend compile; tracing is
    left out because nested jits report it nested), process-wide."""

    _SECONDS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event in self._SECONDS:
            with self._lock:
                self.seconds += secs
                if event == self._SECONDS[-1]:
                    self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.cache_hits, self.seconds

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
