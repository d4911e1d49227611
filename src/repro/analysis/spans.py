"""Spans: named host intervals on the profiler's clock, and the counters
read from them.

``span(name, **meta)`` wraps ``jax.profiler.TraceAnnotation``: while a
profiler session records, the span is an event on the trace's host plane,
on the clock of the device ops, with ``meta`` as its stats. Whether or not
one records, it measures its wall time (``time.perf_counter``) and the
thread's CPU time (``time.thread_time``); on exit it adds both to its trace
event as ``wall_ms`` and ``cpu_ms`` and leaves them on the yielded
:class:`Span`, where the caller reads its counters. A counter and its span
are one measurement. The event's own duration brackets ``wall_ms``: it
starts before and ends after, by the calls between them, or longer where
the thread waits for the GIL there.

Spans nest per thread. A span inherits ``rid`` and ``wave`` from the span
it runs in, and every enclosing span sums the wall time of the spans inside
it by name (``Span.phase_ms``): a request's ``plan.request`` span carries
its plan phases to ``WaveStats`` without a counter per phase. Code under a
span adds to the innermost one's counters with ``count(**n)``
(``Span.counts``, summed; trace stats on exit). Spans mark
stages and phases, never a voxel, tile or loop iteration: off the profiler
one costs a few microseconds.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

#: metadata a nested span takes from the span it runs in
INHERITED = ("rid", "wave")

_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """One timed interval (see the module docstring). Times in ms;
    ``start_ms``/``end_ms`` are on the ``time.perf_counter`` clock."""

    __slots__ = ("name", "meta", "start_ms", "end_ms", "cpu_ms", "phase_ms",
                 "counts", "_tm", "_cpu0")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = {k: v for k, v in meta.items() if v is not None}
        self.start_ms = self.end_ms = self.cpu_ms = 0.0
        self.phase_ms: dict[str, float] = {}
        self.counts: dict[str, int] = {}

    @property
    def wall_ms(self) -> float:
        return self.end_ms - self.start_ms

    def note(self, **meta) -> None:
        """Add metadata known only once the span has run."""
        self._tm.set_metadata(**meta)

    def __enter__(self) -> Span:
        stack = _stack()
        if stack:
            outer = stack[-1].meta
            for k in INHERITED:
                if k in outer:
                    self.meta.setdefault(k, outer[k])
        stack.append(self)
        self._tm = TraceAnnotation(self.name, **self.meta)
        self._tm.__enter__()
        self._cpu0 = time.thread_time()
        self.start_ms = time.perf_counter() * 1e3
        return self

    def __exit__(self, *exc) -> None:
        self.end_ms = time.perf_counter() * 1e3
        self.cpu_ms = (time.thread_time() - self._cpu0) * 1e3
        self._tm.set_metadata(wall_ms=self.wall_ms, cpu_ms=self.cpu_ms,
                              **self.counts)
        self._tm.__exit__(*exc)
        stack = _stack()
        stack.pop()
        for outer in stack:
            outer.phase_ms[self.name] = (outer.phase_ms.get(self.name, 0.0)
                                         + self.wall_ms)


def count(**n: int) -> None:
    """Add ``n`` to the counters of this thread's innermost open span;
    outside any span, nothing."""
    stack = _stack()
    if stack:
        counts = stack[-1].counts
        for k, v in n.items():
            counts[k] = counts.get(k, 0) + int(v)


def span(name: str, **meta) -> Span:
    """A span named ``name`` with trace metadata ``meta`` (``None`` values
    are left out); use it as a context manager."""
    return Span(name, meta)
