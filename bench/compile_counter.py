"""Counts XLA backend compiles and persistent-cache hits through JAX's
monitoring events (copied from the repository's chip smoke script)."""

from __future__ import annotations


class CompileCounter:
    """Backend compiles and their seconds; executables loaded from the
    persistent cache count as hits, not compiles."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.hits = 0, 0.0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.compiles, self.seconds, self.hits
