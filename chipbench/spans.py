"""Host spans around the calls into the system, and compile accounting."""

from __future__ import annotations

import contextlib


class Spans:
    """Named host spans around the calls into the system. With ``trace``
    on, each span is a ``jax.profiler.TraceAnnotation``, so that the
    reduction of the device trace can say what the host was doing in
    each idle gap; with it off, a span costs nothing."""

    def __init__(self, trace: bool = False):
        self._annotation = None
        if trace:
            import jax.profiler

            self._annotation = jax.profiler.TraceAnnotation

    def span(self, name: str):
        if self._annotation is None:
            return contextlib.nullcontext()
        return self._annotation(name)


class CompileClock:
    """Seconds the backend compiler (XLA and Mosaic) spends, and the
    persistent-cache hits, from JAX's own monitoring events. Tracing and
    lowering are left out: their events nest and would count twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits
