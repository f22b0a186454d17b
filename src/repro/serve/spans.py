"""Host spans of the serving engine, on the profiler's clock and in a
bounded buffer of the engine's own.

``SpanRecorder.span(name, **attrs)`` enters
``jax.profiler.TraceAnnotation(name)`` — an event of the profiler's
trace while one is being taken, with the attrs as its stats — and
appends a ``Span`` read on ``time.perf_counter()`` to ``spans``, a deque
that keeps the newest ``SPAN_CAP``. Recording is always on; with no
trace active an annotation costs about half a microsecond, and the attrs
reach the profiler only while a trace is active.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax

# A chunked engine step records about a dozen spans: the step, admit,
# pages, one dispatch per program, one wait per host block, harvest.
# Steps of the on-chip benchmark's cells take 0.3 s and more, so a 48 s
# window and its 24 s warm-up hold under 3,000; the bound leaves room
# for steps five times shorter.
SPAN_CAP = 16384


@dataclasses.dataclass(slots=True)
class Span:
    """One span: its name, start and end on ``time.perf_counter()``, the
    name of the span it was opened in (None at the top), and its attrs."""
    name: str
    t0: float = 0.0
    t1: float = 0.0
    parent: str | None = None
    attrs: dict = dataclasses.field(default_factory=dict)


_latest: SpanRecorder | None = None


def latest() -> SpanRecorder | None:
    """The recorder made last in this process. A reader that runs after
    its engine is freed, such as a benchmark's per-layer metric, finds
    the engine's spans here."""
    return _latest


class SpanRecorder:
    """Nested host spans, recorded whether or not a trace is active."""

    def __init__(self, cap: int = SPAN_CAP):
        global _latest
        self.spans: collections.deque[Span] = collections.deque(maxlen=cap)
        self._open: list[str] = []
        _latest = self

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Yields the ``Span``; attrs set on it before the block ends are
        recorded in the buffer and, while a trace is active, in the
        trace. The span is recorded when the block ends, raised or not."""
        sp = Span(name, parent=self._open[-1] if self._open else None,
                  attrs=attrs)
        self._open.append(name)
        try:
            with jax.profiler.TraceAnnotation(name) as tm:
                sp.t0 = time.perf_counter()
                try:
                    yield sp
                finally:
                    sp.t1 = time.perf_counter()
                    if sp.attrs and tm.is_enabled():
                        tm.set_metadata(**sp.attrs)
        finally:
            self._open.pop()
            self.spans.append(sp)
