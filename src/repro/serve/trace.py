"""Spans and counters for the serve loop.

A `Recorder` keeps, for one `ServeEngine.serve()` call, every span the
loop opened (name, start, end, parent span, request id, attributes) and a
handful of integer counters. Times are on the clock the recorder is reset
with: the engine's `now()`, the clock of the scheduler's stamps.

Each span's body also runs inside a `jax.profiler.TraceAnnotation` named
`repro:<name>`. While a profile is being taken the span then sits on the
host plane of the trace, on the same clock as the device's operations;
while none is, the annotation records nothing.

Opening or closing a span reads the host clock and nothing else: it never
waits for the device. A span around a dispatch measures the dispatch, and
the device trace shows the device time.
"""
from __future__ import annotations

import time
from typing import Any, Callable

import jax

PREFIX = "repro:"


class Span:
    """One recorded interval; also the context manager that records it."""

    __slots__ = ("name", "rid", "parent", "start", "end", "attrs", "_rec",
                 "_ann")

    def __init__(self, rec: "Recorder", name: str, rid: int | None,
                 attrs: dict[str, Any]):
        self._rec, self.name, self.rid, self.attrs = rec, name, rid, attrs
        self.parent: int | None = None   # index of the enclosing span
        self.start = self.end = None

    @property
    def dur(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        rec = self._rec
        meta = self.attrs if self.rid is None else dict(self.attrs,
                                                        rid=self.rid)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name, **meta)
        self._ann.__enter__()
        self.parent = rec._open[-1] if rec._open else None
        rec._open.append(len(rec.spans))
        rec.spans.append(self)
        self.start = rec.clock()
        return self

    def __exit__(self, *exc) -> None:
        self.end = self._rec.clock()
        self._rec._open.pop()
        self._ann.__exit__(*exc)


class Recorder:
    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self.reset(clock)

    def reset(self, clock: Callable[[], float] | None = None):
        """Forget every span and counter; later spans read `clock`."""
        if clock is not None:
            self.clock = clock
        self.spans: list[Span] = []
        self.counters: dict[str, int] = {}
        self._open: list[int] = []

    def span(self, name: str, rid: int | None = None, **attrs) -> Span:
        """`with recorder.span(...)`: record the body as one span, nested
        in the span open around it."""
        return Span(self, name, rid, attrs)

    def count(self, name: str, n: int = 1):
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def total(self, name: str, **where) -> float:
        """Summed duration of the closed spans called `name` whose
        attributes hold every value in `where`."""
        return sum(s.dur for s in self.spans
                   if s.name == name and s.end is not None
                   and all(s.attrs.get(k) == v for k, v in where.items()))

    def last(self, name: str) -> Span | None:
        """The span called `name` opened most recently."""
        return next((s for s in reversed(self.spans) if s.name == name),
                    None)

    def self_s(self, i: int) -> float:
        """Span i's duration less the time its child spans cover (the
        loop is one thread, so children never overlap)."""
        return self.spans[i].dur - sum(c.dur for c in self.spans
                                       if c.parent == i)

    def summary(self) -> dict:
        """{"spans": {name: {"n", "s"}}, "counters": {name: n}}."""
        spans: dict[str, dict] = {}
        for s in self.spans:
            if s.end is not None:
                t = spans.setdefault(s.name, {"n": 0, "s": 0.0})
                t["n"] += 1
                t["s"] += s.dur
        return {"spans": spans, "counters": dict(self.counters)}
