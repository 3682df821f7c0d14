"""Host spans of the serving engine, on the profiler's clock.

``Spans.span(name)`` opens ``jax.profiler.TraceAnnotation("engine.<name>")``
(so a ``jax.profiler`` trace shows it on the same clock as the device's
programs) and reads ``time.perf_counter()`` at both ends, adding the elapsed
time to the name's total seconds, count and maximum.  The annotation is
made only while a profiler records (it decides at construction whether to
record), so with none running a span costs two clock reads and a few
attribute updates.  The engine's ``phase_s`` is rolled up from these
readings.

Each name has one :class:`Span`, reused by every ``with``: a span never
opens inside itself, and its ``t0``/``t1``/``s`` hold the last reading until
the name is opened again.
"""
from __future__ import annotations

from time import perf_counter
from typing import Dict

from jax.profiler import TraceAnnotation

PREFIX = "engine."
_recording = TraceAnnotation.is_enabled


class Span:
    """One name's span: the last reading (``t0``, ``t1``, ``s``) and the
    totals (``total_s``, ``n``, ``max_s``)."""

    __slots__ = ("label", "_ann", "t0", "t1", "total_s", "n", "max_s")

    def __init__(self, name: str):
        self.label = PREFIX + name
        self._ann = None
        self.t0 = self.t1 = self.total_s = self.max_s = 0.0
        self.n = 0

    @property
    def s(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        if _recording():
            self._ann = TraceAnnotation(self.label)
            self._ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = self.t1 = perf_counter()
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(*exc)
        dt = t1 - self.t0
        self.total_s += dt
        self.n += 1
        if dt > self.max_s:
            self.max_s = dt


class Spans:
    """The engine's spans by name."""

    def __init__(self):
        self._spans: Dict[str, Span] = {}

    def span(self, name: str) -> Span:
        s = self._spans.get(name)
        if s is None:
            s = self._spans[name] = Span(name)
        return s

    def summary(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"s", "n", "max_s"}}``, a copy."""
        return {k: {"s": s.total_s, "n": s.n, "max_s": s.max_s}
                for k, s in self._spans.items()}
