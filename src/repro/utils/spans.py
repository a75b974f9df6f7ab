"""Spans: the one way the program times its own stages.

``with span("serve.prefill", batch=2) as sp:`` does two things:

* it enters ``jax.profiler.TraceAnnotation(name)``, so that in a profile
  the stage lands on the host plane, on the same clock as the device's
  operations (only once the process has imported JAX: before that there
  is no profiler to write to);
* on exit it appends a :class:`Record` (name, ``time.perf_counter``
  start and end, thread, its own id, its parent's, its root's, attrs)
  to a bounded in-process buffer of :data:`CAPACITY` records, the oldest
  dropped first.

A span nests under the innermost open span of its own thread and shares
that span's root id; a span opened with no span open on its thread is a
root, unless the thread was started under :func:`carry`, in which case
it carries the root of the thread that started it (lane threads, the
build pool).  Spans are always on and cost about a microsecond; none
sits in a jitted function or on a kernel's launch path.  Counters that
time an interval (``compile_s``, ``static_s``, ``learn_s``,
``ServeEngine.stats["prefill_s"]``) read ``sp.seconds`` of the span over
that interval, so the two can never disagree.

:func:`snapshot` returns the records and :func:`reset` clears them;
records stay in memory until a reader takes them.  Process lanes
(``ProcessExecutor``) ship no spans back: what a worker process times
stays in that process.
"""

from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import NamedTuple, Optional

__all__ = ["CAPACITY", "Record", "span", "carry", "snapshot", "reset"]

#: records kept; a long run drops its oldest
CAPACITY = 1 << 16


class Record(NamedTuple):
    name: str
    start: float  # time.perf_counter()
    end: float
    thread: int  # threading.get_ident()
    id: int
    parent: Optional[int]  # the enclosing span on the same thread
    root: int  # the outermost span of one generate() call or tuned workload
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


_records: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)


class _Local(threading.local):
    def __init__(self):
        self.stack: list = []  # open spans of this thread, innermost last
        self.root: Optional[int] = None  # carried from the starting thread
        self.thread = threading.get_ident()


_local = _Local()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is imported


def _annotation_class():
    global _annotation
    if "jax" in sys.modules:  # before that, there is no profiler to write to
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class span:
    """A timed stage; see the module docstring.  ``attrs`` may be added
    to while the span is open (``sp.attrs["bucket"] = 4096``)."""

    __slots__ = ("name", "attrs", "id", "parent", "root", "start", "end", "_ann", "_local")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        local = self._local = _local
        stack = local.stack
        self.id = next(_ids)
        if stack:
            top = stack[-1]
            self.parent, self.root = top.id, top.root
        else:
            self.parent, self.root = None, local.root or self.id
        stack.append(self)
        ann = _annotation or _annotation_class()
        if ann is not None:
            ann = ann(self.name)
            ann.__enter__()
        self._ann = ann
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        local = self._local
        local.stack.pop()
        # a plain tuple here; snapshot() makes the Records
        _records.append((
            self.name, self.start, self.end, local.thread,
            self.id, self.parent, self.root, self.attrs,
        ))

    @property
    def seconds(self) -> float:
        """Duration, once the span has closed."""
        return self.end - self.start


def carry(fn):
    """``fn`` wrapped to run, on whatever thread calls it, under the root
    of the span open here: spans it opens on an empty stack join this
    root instead of starting their own."""
    stack = _local.stack
    root = stack[-1].root if stack else _local.root

    def run(*args, **kwargs):
        prev, _local.root = _local.root, root
        try:
            return fn(*args, **kwargs)
        finally:
            _local.root = prev

    return run


def snapshot() -> list:
    """The buffered records, oldest first (each appended as it closed)."""
    return [Record._make(r) for r in _records.copy()]


def reset() -> None:
    _records.clear()
