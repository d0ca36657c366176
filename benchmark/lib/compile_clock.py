"""Seconds jax itself reports for tracing and for XLA compilation (or the
load from the persistent cache that takes its place), the cache's hits and
misses, and how many compile events fell after a mark — from
jax.monitoring. Copied from ``chip_smoke.CompileClock`` (PR 21), with the
mark added: the benchmark places it at the window's start, and
``compiles_in_window`` is what it counts from there."""
from __future__ import annotations

import collections
import threading


class CompileClock:
    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()  # pipelines compile on their threads
        self._sum = collections.Counter()
        self._compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        with self._lock:
            self._sum[event] += duration
            if event == self.COMPILE:
                self._compiles += 1

    def _event(self, event, **_):
        with self._lock:
            self._sum[event] += 1

    def read(self) -> dict:
        with self._lock:
            s = dict(self._sum)
            n = self._compiles
        return {"trace_s": sum(s.get(e, 0.0) for e in self.TRACE),
                "compile_s": s.get(self.COMPILE, 0.0),
                "compiles": n,
                "cache_hits": s.get(self.HIT, 0),
                "cache_misses": s.get(self.MISS, 0)}
