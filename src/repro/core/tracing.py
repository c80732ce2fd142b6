"""Spans and counters at the boundaries of the checkpoint layers.

A span is one phase of a dump or restore (``ckpt.capture``,
``ckpt.tree_digest``, ``ckpt.dump.chunks``, ...); a counter is chunk-level
work added up inside it (``chunk_hash``, ``chunk_write``, ``fsync``, ...).
Recording is always on: phase spans are few (about ten per dump or
restore) and chunk-level work costs one counter increment, never a span.

- ``span(name, **attrs)`` records name, start, end, span id and parent id
  on ``time.perf_counter`` and opens a ``jax.profiler.TraceAnnotation`` of
  the same name, so the phase shows in a profile too (that costs something
  only while a profile is being collected). The parent is the span open on
  the caller's ``contextvars`` context; the executor runs its pool tasks
  under a copy of the submitter's context, so work fanned out to the
  ``ckpt-cpu``/``ckpt-io`` threads counts into the span that submitted it.
  ``span(name, rss=True)`` also records the host's resident set
  (``rss_bytes``) at its end when the span has no parent: the roots of a
  migrate, restore or dump. Only those, because reading it releases the
  interpreter lock, and on a thread that returns to the training loop
  (the end of an async save) taking the lock back behind busy pool threads
  can cost tenths of a second.
- ``count(name, n=1, nbytes=0, seconds=0.0)`` adds to every open span on
  the context and to the process totals. Each counter keeps ``count``,
  ``bytes``, ``seconds`` and ``max_s`` (the slowest single increment).
  Seconds are thread-seconds: work done on eight pool threads at once adds
  eight times its wall time.
- ``spans()``, ``counters()`` and ``reset()`` read or clear the records.
  Finished spans go to a buffer of the last ``SPAN_BUFFER``, so a long job
  does not grow without limit.

Example::

    from repro.core import tracing
    tracing.reset()
    sess.migrate(MigrateRequest(state=state))
    for s in tracing.spans():
        print(s["name"], s["t1"] - s["t0"], s["counters"].get("fsync"))
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import os
import threading
import time

import jax

SPAN_BUFFER = 4096

_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_tracing_span", default=None)
_lock = threading.Lock()
_ids = itertools.count(1)
_finished: collections.deque = collections.deque(maxlen=SPAN_BUFFER)
_totals: dict = {}
_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


class Counter:
    """count, bytes, thread-seconds and the largest single increment's
    seconds of one kind of work."""
    __slots__ = ("count", "bytes", "seconds", "max_s")

    def __init__(self):
        self.count = self.bytes = 0
        self.seconds = self.max_s = 0.0

    def add(self, n: int, nbytes: int, seconds: float):
        self.count += n
        self.bytes += nbytes
        self.seconds += seconds
        if seconds > self.max_s:
            self.max_s = seconds

    def as_dict(self) -> dict:
        return {"count": self.count, "bytes": self.bytes,
                "seconds": self.seconds, "max_s": self.max_s}


class Span:
    """One recorded phase; ``t1`` is None while it is open."""
    __slots__ = ("name", "id", "parent", "t0", "t1", "attrs", "counters")

    def __init__(self, name: str, parent: "Span | None", attrs: dict):
        self.name, self.parent, self.attrs = name, parent, attrs
        self.id = next(_ids)
        self.t0 = self.t1 = None
        self.counters: dict = {}

    def set(self, **attrs):
        """Add attributes (e.g. ``bytes``) once they are known."""
        self.attrs.update(attrs)

    @property
    def duration_s(self) -> float | None:
        return None if self.t1 is None else self.t1 - self.t0

    def counter(self, name: str) -> dict:
        """This span's counter ``name`` as a dict (zeros if never added)."""
        with _lock:
            c = self.counters.get(name)
            return (c or Counter()).as_dict()

    def as_dict(self) -> dict:
        with _lock:
            counters = {k: c.as_dict() for k, c in self.counters.items()}
        return {"name": self.name, "id": self.id,
                "parent": self.parent.id if self.parent else None,
                "t0": self.t0, "t1": self.t1, "attrs": dict(self.attrs),
                "counters": counters}


def rss_bytes() -> int | None:
    """The process's resident set now, from /proc/self/statm (None where
    there is no /proc)."""
    try:
        with open("/proc/self/statm", "rb") as f:
            return int(f.read().split()[1]) * _PAGE
    except (OSError, IndexError, ValueError):
        return None


@contextlib.contextmanager
def span(name: str, *, rss: bool = False, **attrs):
    """Record the enclosed block as span ``name``; yields the Span."""
    parent = _current.get()
    sp = Span(name, parent, attrs)
    token = _current.set(sp)
    try:
        with jax.profiler.TraceAnnotation(name):
            sp.t0 = time.perf_counter()
            try:
                yield sp
            finally:
                sp.t1 = time.perf_counter()
    finally:
        _current.reset(token)
        if rss and parent is None:
            sp.attrs["rss_bytes"] = rss_bytes()
        _finished.append(sp)    # atomic; no lock to wait for here


def count(name: str, n: int = 1, nbytes: int = 0, seconds: float = 0.0):
    """Add to counter ``name`` of every open span on the context and of the
    process totals."""
    sp = _current.get()
    with _lock:
        c = _totals.get(name)
        if c is None:
            c = _totals[name] = Counter()
        c.add(n, nbytes, seconds)
        while sp is not None:
            if sp.t1 is None:
                c = sp.counters.get(name)
                if c is None:
                    c = sp.counters[name] = Counter()
                c.add(n, nbytes, seconds)
            sp = sp.parent


def submit(pool, fn, *args):
    """``pool.submit(fn, *args)`` under a copy of the caller's context, so
    the task counts into the span that submitted it."""
    return pool.submit(contextvars.copy_context().run, fn, *args)


def spans() -> list:
    """Finished spans, oldest first, as dicts: name, id, parent (id), t0,
    t1 (``time.perf_counter`` seconds), attrs and counters."""
    return [s.as_dict() for s in list(_finished)]


def counters() -> dict:
    """Process totals of every counter since the last reset()."""
    with _lock:
        return {k: c.as_dict() for k, c in _totals.items()}


def reset():
    """Forget finished spans and process totals (open spans keep theirs)."""
    _finished.clear()
    with _lock:
        _totals.clear()
