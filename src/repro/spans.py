"""Host spans of the program, kept in memory and shown to the profiler.

``with span("repro.sweep.pack") as s: ...`` times its body.  While the JAX
profiler runs, the span is also a ``jax.profiler.TraceAnnotation`` on the
trace's host plane, beside the device operations.  Whether or not it runs,
the span is appended on exit -- a raising body included -- to a bounded
in-memory ring that :func:`recorded` copies out, so a reader in the same
process can read spans that started before the profiler did; the ring
drops its oldest spans when full, and :func:`dropped` counts them.

Each record is ``(id, parent_id, name, start_ns, end_ns, attrs)``.
Timestamps come from ``time.time_ns()``, the realtime clock the profiler
stamps host events with, so a record and its event in the trace agree.
The parent is the innermost span open on the same thread (``None`` at the
top).  ``attrs`` are set while the span is open (``s.set(lanes=64)``):
they carry the counts, recorded at the same boundaries as the time.  A
span whose body raises is recorded with ``attrs["error"]``, the
exception's type name.

Recording is always on.  Names start with ``repro.``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax

CAPACITY = 1 << 16


class Record(NamedTuple):
    id: int
    parent_id: Optional[int]
    name: str
    start_ns: int
    end_ns: int
    attrs: Dict[str, Any]


class Span:
    """One span: the handle ``with span(...) as s`` yields, open from
    ``__enter__`` to ``__exit__``."""

    __slots__ = ("_rec", "_ann", "id", "parent_id", "name", "start_ns",
                 "attrs")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = self._rec._stack()
        self.id = next(self._rec._ids)
        self.parent_id = stack[-1].id if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, typ, value, tb) -> None:
        end = time.time_ns()
        self._ann.__exit__(typ, value, tb)
        self._rec._stack().pop()
        if typ is not None:
            self.attrs["error"] = typ.__name__
        self._rec._close(Record(self.id, self.parent_id, self.name,
                                self.start_ns, end, dict(self.attrs)))


class Recorder:
    """A ring of the last ``capacity`` closed spans; the oldest are
    dropped first, and counted."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.dropped = 0

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def _close(self, rec: Record) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(rec)

    def recorded(self) -> List[Record]:
        with self._lock:
            return list(self._ring)


_RECORDER = Recorder()


def span(name: str, **attrs) -> Span:
    """A span of the process-wide record; see the module docstring."""
    return _RECORDER.span(name, **attrs)


def recorded() -> List[Record]:
    """A copy of the process-wide record, oldest first."""
    return _RECORDER.recorded()


def dropped() -> int:
    """How many spans the process-wide record has dropped, oldest first:
    a reader whose window starts before the oldest kept span has lost
    part of it."""
    return _RECORDER.dropped
