"""Compile-time accounting on ``jax.monitoring`` (copied from chip_smoke.py's
``CompileClock``; that original is listed in PERF.md for a later PR to drop).

Cold = XLA compiles, warm = persistent-cache retrievals: both land in the
backend-compile event, so ``programs`` counts either and ``cache_misses``
tells them apart. The harness marks the clock when the window opens; a
program compiled or loaded inside the window makes the run ``correct: false``.
"""

from __future__ import annotations


class CompileClock:
    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += float(secs)
            self.programs += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.seconds, self.programs, self.cache_hits,
                self.cache_misses)

    def since(self, mark):
        s, p, h, m = mark
        return {
            "compile_s": self.seconds - s,
            "programs": self.programs - p,
            "cache_hits": self.cache_hits - h,
            "cache_misses": self.cache_misses - m,
        }
