"""Host spans of the program, on the profiler's clock.

Every span the program opens goes through this module. :func:`span`
always writes a ``jax.profiler.TraceAnnotation``, so it lands in any
profiler capture (``jax.profiler.trace`` / ``start_trace`` /
``start_server``) beside the device's operations. While such a session
records, each span is also kept in memory as a :class:`Span`, which
:func:`spans` returns: the numbers a capture's own reader can take
without parsing the trace file. With no session recording a span costs
the annotation and one flag check, and nothing is kept.

Times are ``time.time_ns()``: the clock the profiler stamps host events
with (an xplane host event's ``start_ns`` plus its ``Task Environment``
plane's ``profile_start_time`` stat is the same number).

The buffer holds one session: a span or record made while no session
records marks it closed, and the first record of the next session then
clears it. A span entered before the session began is no record's
parent. Names start with ``vfl.``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Deque, List, NamedTuple, Optional

import jax

MAX_RECORDS = 1 << 16


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]    # the enclosing open span's name, if any
    ids: dict                # e.g. ``rid=``, ``k=``, ``after=``

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_records: Deque[Span] = collections.deque(maxlen=MAX_RECORDS)
_closed = True
_local = threading.local()


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def _recording() -> bool:
    """Is a profiler session recording? Marks the buffer closed if not."""
    global _closed
    if jax.profiler.TraceAnnotation.is_enabled():
        return True
    _closed = True
    return False


def _keep(rec: Span) -> None:
    global _closed
    if _closed:
        _records.clear()
        _closed = False
    _records.append(rec)


class _Open:
    """The context manager :func:`span` returns."""
    __slots__ = ("name", "ids", "_annotation", "_start")

    def __init__(self, name: str, ids: dict) -> None:
        self.name, self.ids = name, ids
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)
        self._start: Optional[int] = None

    def __enter__(self) -> "_Open":
        self._annotation.__enter__()
        if _recording():
            _stack().append(self.name)
            self._start = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._start is not None:
            end = time.time_ns()
            stack = _stack()
            stack.pop()
            _keep(Span(self.name, self._start, end,
                       stack[-1] if stack else None, self.ids))
        self._annotation.__exit__(*exc)


def span(name: str, **ids) -> _Open:
    """``with span("vfl.sched.block", k=8):`` — an annotation in any
    capture, and a :class:`Span` kept while a session records."""
    return _Open(name, ids)


def record(name: str, start_ns: int, end_ns: int, **ids) -> None:
    """Keep an interval that began in the past (a request's wait in the
    queue, the device's drained time), under :func:`span`'s rule. It is
    not written into the capture; its parent is the innermost open span."""
    if _recording():
        stack = _stack()
        _keep(Span(name, int(start_ns), int(end_ns),
                   stack[-1] if stack else None, ids))


def step(name: str, step_num: int) -> jax.profiler.StepTraceAnnotation:
    """XProf's step marker around one training step (its step-time
    breakdown reads it). Not kept in memory."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


def spans() -> List[Span]:
    """The records of the newest profiler session, oldest first."""
    return list(_records)
