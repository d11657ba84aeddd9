"""Process-wide XLA compiles, their seconds and persistent-cache hits,
counted from JAX's monitoring events."""
from __future__ import annotations


class CompileMeter:
    def __init__(self):
        import jax
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.seconds, self.cache_hits
