"""Host-phase spans of the search path: where the host's time goes inside
a drain, on the clock the benchmark uses (``time.perf_counter``).

    from repro.engine import spans

    with spans.span("engine.marshal"):
        ...

Spans are recorded exactly while a ``jax.profiler`` trace records host
events: starting a trace turns the recorder on.  Off, :func:`span`
returns one shared object whose ``with`` does nothing (no clock read, no
allocation, no profiler annotation).  On, a span stamps its start and end
with ``time.perf_counter_ns()``, keeps its parent on a per-thread stack,
and opens a ``jax.profiler.TraceAnnotation`` of the same name, so it also
lands on the profiler's host plane beside the device's programs.
Finished spans wait in a list until :func:`take`; nothing is written
while serving.

The list holds the spans of the latest trace only: the first span
recorded after the recorder was seen off starts it again from empty.  It
keeps at most :data:`CAPACITY` spans (a few hundred drains, at about 124
spans a drain of 256 mixed requests) and counts the rest as dropped.  So
a server profiled for a diagnosis, with nothing calling :func:`take`,
keeps at most that many spans, those of its latest trace.

Each span carries the drain id of its thread (:func:`next_drain`, called
by the server as a drain starts), so a drain's spans group together.
Backend compiles are recorded as ``jax.compile`` spans
``[end - seconds, end]`` under the span open in the compiling thread.

The recorder is process-wide, like the profiler it follows.  No span is
named ``engine.search``: the benchmark annotates each
``QueryEngine.search`` call under that name and counts the annotations.
"""
from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from time import perf_counter_ns

import jax
from jax._src import dispatch as _dispatch

#: finished spans kept for :func:`take`; later ones are counted as dropped
CAPACITY = 1 << 15

_profiling = jax.profiler.TraceAnnotation.is_enabled
_local = threading.local()
_lock = threading.Lock()
_ids = itertools.count(1)
_drains = itertools.count(1)
_done: list = []
_dropped = 0
_stale = False      # the recorder was seen off since the kept spans began


@dataclass
class Span:
    """One finished span; ``start``/``end`` are ``perf_counter_ns``."""
    name: str
    start: int
    end: int
    id: int
    parent: int | None = None
    drain: int | None = None
    attrs: dict = field(default_factory=dict)


class _Off:
    """The shared span of a recorder that is off."""
    __slots__ = ()
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


class _Timer(_Off):
    """A :func:`timed` span while the recorder is off: the clock only."""
    __slots__ = ("start", "end")

    def __enter__(self):
        self.start = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end = perf_counter_ns()
        return False

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


class _On(_Timer):
    """An open span of a recorder that is on."""
    __slots__ = ("name", "attrs", "id", "parent", "drain", "_mark")
    live = True

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else None
        self.drain = getattr(_local, "drain", None)
        stack.append(self)
        self._mark = jax.profiler.TraceAnnotation(self.name)
        self._mark.__enter__()
        self.start = perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end = perf_counter_ns()
        self._mark.__exit__(*exc)
        _stack().pop()
        _finish(Span(self.name, self.start, self.end, self.id, self.parent,
                     self.drain, self.attrs))
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _finish(s: Span) -> None:
    global _dropped
    with _lock:
        if len(_done) < CAPACITY:
            _done.append(s)
        else:
            _dropped += 1


def _recording() -> bool:
    """Whether a profiler trace records host events; the first call that
    finds one after finding none empties the kept spans."""
    global _stale, _done, _dropped
    if not _profiling():
        _stale = True
        return False
    if _stale:
        with _lock:
            if _stale:
                _done, _dropped, _stale = [], 0, False
    return True


def span(name: str, **attrs):
    """Context manager for one span; ``as s`` gives ``s.live`` (recorded)
    and ``s.set(**attrs)`` for attributes known only inside."""
    if not _recording():
        return _OFF
    return _On(name, attrs)


def timed(name: str, **attrs):
    """As :func:`span`, but timed even while the recorder is off:
    ``s.seconds`` after the ``with`` (the span's own stamps when it is
    recorded, so the work is timed once)."""
    if not _recording():
        return _Timer()
    return _On(name, attrs)


def next_drain() -> int:
    """A new drain id for the calling thread's later spans."""
    _local.drain = next(_drains)
    return _local.drain


def take() -> tuple[list, int]:
    """The finished spans and the count dropped past :data:`CAPACITY`
    since the last take or the latest trace's start; both start again
    from empty."""
    global _done, _dropped
    with _lock:
        out, dropped = _done, _dropped
        _done, _dropped = [], 0
    return out, dropped


def _on_compile(event: str, seconds: float, **_) -> None:
    if event != _dispatch.BACKEND_COMPILE_EVENT:
        return
    end = perf_counter_ns()
    if _recording():
        stack = _stack()
        _finish(Span("jax.compile", end - int(seconds * 1e9), end, next(_ids),
                     stack[-1].id if stack else None,
                     getattr(_local, "drain", None),
                     {"seconds": seconds}))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
