"""Spans and counters of the serving engine's tick, kept as totals.

``VigServeEngine`` owns one ``EngineTracer`` (``engine.tracer``). It is
off by default: a span then costs one attribute check and hands back a
shared null span, and nothing is recorded. A caller that measures the
engine switches it on:

* ``recording`` keeps, per span name, the calls, total and self
  nanoseconds (``time.perf_counter_ns``), and the counters: ``host_pulls``
  (device-to-host transfers made through ``to_host``) and what the engine
  adds through ``count`` (``reset_calls`` and ``reset_rows``: compiled
  state-row resets and the slots they reset; ``digc_lists`` and
  ``digc_candidates``: the neighbour entries a tick's graphs hold and
  the top-(k*d) candidates DIGC keeps for them, summed over blocks and
  live lanes from the stage plans; ``digc_tiles``: the co-node tiles
  the ``blocked`` tier's blocks stream in a tick). A span's self time
  is its duration less the time its child spans cover, so the self
  times of a root and everything under it add up to the root's
  duration.
* ``annotating`` (with ``recording``, while a profiler trace runs) also
  enters a ``jax.profiler.TraceAnnotation`` of the span's name, which
  puts the span on the profiler's clock beside the device ops.

Records are totals, not per-event lists: memory stays bounded however
long the engine serves. Read them with ``totals()`` and take deltas.
"""

from __future__ import annotations

import time

import jax
import numpy as np


class _NullSpan:
    """The span handed out while the tracer is off: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **meta) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "meta", "t0", "child_ns", "ann")

    def __init__(self, tracer: "EngineTracer", name: str, meta: dict):
        self.tracer = tracer
        self.name = name
        self.meta = meta
        self.child_ns = 0
        self.ann = None

    def __enter__(self):
        tr = self.tracer
        if tr.annotating:
            self.ann = jax.profiler.TraceAnnotation(self.name, **self.meta)
            self.ann.__enter__()
        tr._open.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        tr = self.tracer
        tr._open.pop()
        if tr._open:
            tr._open[-1].child_ns += dur
        rec = tr._spans.get(self.name)
        if rec is None:
            rec = tr._spans[self.name] = [0, 0, 0]
        rec[0] += 1
        rec[1] += dur
        rec[2] += dur - self.child_ns
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False

    def set(self, **meta) -> None:
        """Add annotation metadata known only after the span opened."""
        if self.ann is not None:
            self.ann.set_metadata(**meta)


class EngineTracer:
    """Named spans and counters of one engine (see the module docstring)."""

    def __init__(self):
        self.recording = False
        self.annotating = False
        self._open: list[_Span] = []
        self._spans: dict[str, list[int]] = {}  # name -> [calls, ns, self ns]
        self._counters: dict[str, int] = {}

    def span(self, name: str, **meta):
        """A context manager timing ``name``; ``meta`` goes on the
        profiler annotation (``name#k=v,...#``) when annotating."""
        if not self.recording:
            return NULL_SPAN
        return _Span(self, name, meta)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name`` while recording."""
        if self.recording:
            self._counters[name] = self._counters.get(name, 0) + n

    def to_host(self, x) -> np.ndarray:
        """``np.asarray(x)``; a device array's copy to the host is counted
        as one ``host_pulls``."""
        if isinstance(x, jax.Array):
            self.count("host_pulls")
        return np.asarray(x)

    def totals(self) -> dict:
        """``{"spans": {name: {"calls", "total_s", "self_s"}},
        "counters": {name: n}}`` since the engine was built."""
        return {
            "spans": {name: {"calls": c, "total_s": t * 1e-9,
                             "self_s": s * 1e-9}
                      for name, (c, t, s) in self._spans.items()},
            "counters": dict(self._counters),
        }
