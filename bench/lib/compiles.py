"""Counts XLA compilations and persistent-cache hits in this process, and
the seconds JAX spent tracing, lowering, compiling and loading programs,
by JAX's own monitoring events."""

from __future__ import annotations


class CompileCounter:
    def __init__(self) -> None:
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        # tracing, lowering and compiling (or loading from the persistent
        # cache); "/jax/compilation_cache/compile_time_saved_sec" and the
        # like report time not spent, and are left out
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
