"""Host spans and counters of the serving path, on the profiler's clock.

``Spans.span(name)`` times one block of host work: it opens a
``jax.profiler.TraceAnnotation("sr.<name>")``, so the block lands on a
profiler trace's host plane on the clock the device planes use, and
appends the block's milliseconds to the series ``name``.
``Spans.record(name, ms)`` appends a duration measured between two
points in different calls (a request's queue wait, a dispatch's device
time).  Each series is a bounded ``deque``: a long-lived server keeps the
last ``maxlen`` values.  There is no switch: with no profiler session
active the annotation is a no-op.

A block that raises records nothing: a failed launch is not a sample of
launch time.  No lock: ``deque.append`` and ``clear`` are atomic, and
readers snapshot a series with :meth:`Spans.values`.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Spans", "MAXLEN", "PREFIX", "clock"]

MAXLEN = 65_536  # values kept per series
PREFIX = "sr."  # every span's name on the profiler's host plane
clock = time.perf_counter


class _Span:
    """One timed block; ``t0``/``t1`` (``clock()`` seconds) and ``ms``
    are readable after it closes."""

    __slots__ = ("_series", "_annotation", "t0", "t1", "ms")

    def __init__(self, series: Deque[float], name: str):
        self._series = series
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> "_Span":
        self._annotation.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = clock()
        self._annotation.__exit__(exc_type, exc, tb)
        self.ms = (self.t1 - self.t0) * 1e3
        if exc_type is None:
            self._series.append(self.ms)
        return False


class Spans:
    """Named bounded series of milliseconds, fed by spans and counters."""

    def __init__(self, maxlen: int = MAXLEN):
        self.maxlen = int(maxlen)
        self._series: Dict[str, Deque[float]] = {}

    def series(self, name: str) -> Deque[float]:
        """The live series ``name`` (created empty on first use)."""
        s = self._series.get(name)
        if s is None:
            s = self._series.setdefault(name, deque(maxlen=self.maxlen))
        return s

    def values(self, name: str) -> Tuple[float, ...]:
        """A snapshot of the series ``name``."""
        return tuple(self.series(name))

    def span(self, name: str) -> _Span:
        """``with spans.span(name):`` times the block as ``sr.<name>``."""
        return _Span(self.series(name), PREFIX + name)

    def record(self, name: str, ms: float) -> None:
        self.series(name).append(float(ms))

    def reset(self) -> None:
        for s in self._series.values():
            s.clear()
