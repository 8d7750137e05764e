"""Spans of the serving hot path, on the host clock and the profiler's.

A `Tracer` records each span as a `Record` ``(id, name, t0, t1, parent,
epoch, args)`` on ``time.perf_counter()``, into a ring of fixed
capacity, so a long-lived server does not grow.  ``parent`` is the id of
the span that was open around it; ``epoch`` counts `WISPServer.step`
calls.  While a profiler session runs, each span also enters a
``jax.profiler.TraceAnnotation`` of the same name: that puts it on the
host plane of the device trace, on the trace's own clock, with ``epoch``
and its args as the event's stats.

`WISPServer` and `VerificationEngine` take ``tracer=None``.  Without a
tracer, `span` hands back one shared no-op object; `timed` (the spans
whose durations are the engine's ``t_verify`` and ``t_chunk``) still
reads the clock at both ends.  Counters stay in ``engine.stats``.  A
tracer serves one thread.
"""
from __future__ import annotations

import collections
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: records a tracer keeps (the oldest are dropped first)
CAPACITY = 1 << 17


class Record(NamedTuple):
    id: int
    name: str
    t0: float            # time.perf_counter() seconds
    t1: float
    parent: int          # id of the enclosing span, -1 if none
    epoch: int           # the server step it fell in (0 before the first)
    args: dict


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **args):
        pass


#: the span every call hands back when there is no tracer
NOOP = _Noop()


class Span:
    """One span: its clock readings ``t0``/``t1`` (``dt``), and with a
    tracer its record and profiler annotation.  ``set`` adds args known
    only inside the span."""

    __slots__ = ("tracer", "name", "args", "t0", "t1", "id", "parent",
                 "_ann")

    def __init__(self, tracer, name: str, args: dict):
        self.tracer, self.name, self.args = tracer, name, args
        self._ann = None

    def __enter__(self):
        tr = self.tracer
        if tr is not None:
            self.id, self.parent = tr._open()
            if TraceAnnotation.is_enabled():
                self._ann = TraceAnnotation(self.name, epoch=tr.epoch,
                                            **self.args)
                self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        tr = self.tracer
        if tr is not None:
            if self._ann is not None:
                self._ann.__exit__(*exc)
            tr._close(self)
        return None

    def set(self, **args):
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    @property
    def dt(self) -> float:
        return self.t1 - self.t0


class Tracer:
    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._stack: list = []           # ids of the open spans
        self._next = 0
        #: the current server step (`WISPServer.step` advances it)
        self.epoch = 0

    def span(self, name: str, **args) -> Span:
        """A span as a context manager; recorded when it closes."""
        return Span(self, name, args)

    def interval(self, name: str, t0: float, t1: float, ids, **args):
        """Record a span that no ``with`` block can hold (a request's wait
        in the queue): ``ids`` joins it to the spans that served it."""
        self._next += 1
        self._ring.append(Record(self._next, name, t0, t1, -1, self.epoch,
                                 dict(args, ids=ids)))

    def records(self) -> list:
        """The kept records, in the order they closed."""
        return list(self._ring)

    def _open(self) -> tuple[int, int]:
        self._next += 1
        parent = self._stack[-1] if self._stack else -1
        self._stack.append(self._next)
        return self._next, parent

    def _close(self, s: Span):
        self._stack.pop()
        self._ring.append(Record(s.id, s.name, s.t0, s.t1, s.parent,
                                 self.epoch, s.args))


def span(tracer: Tracer | None, name: str, **args):
    """``tracer.span(name, **args)``, or `NOOP` without a tracer."""
    return NOOP if tracer is None else Span(tracer, name, args)


def timed(tracer: Tracer | None, name: str, **args) -> Span:
    """A span whose clock readings are taken with or without a tracer."""
    return Span(tracer, name, args)
