"""Compile seconds and counts from ``jax.monitoring`` (a copy of
``chip_smoke.CompileMeter``, with a count of compiles)."""
from __future__ import annotations


class CompileMeter:
    """Seconds JAX spends compiling (or loading from the persistent
    cache), the number of backend compiles, and the cache's hits and
    misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.seconds, self.compiles, self.hits, self.misses

    def since(self, mark) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.compiles - mark[1],
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}
