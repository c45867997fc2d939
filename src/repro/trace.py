"""Spans of the served path, kept in memory and stamped on the profiler's
clock.

Off by default.  While off, every span site costs one check of the
module-level recorder: no clock is read, no object is built and no device
sync is added.  ``start()`` turns recording on; ``stop()`` turns it off
and returns every span closed in between, as ``Span`` rows
``(id, name, t0_ns, t1_ns, parent, flush)`` on ``time.perf_counter_ns``.
``parent`` is the id of the enclosing span (-1 for a root) and ``flush``
the id of the flush that caused it (-1 outside any flush), so a request's
queue wait (``Request.flush``) and its flush's spans join on one id.
While recording, each span also opens a ``jax.profiler.TraceAnnotation``
named ``repro.<name>``: under a profiler session the span then lands on
the host plane, on the same clock as the device's ops.

Spans sit at call granularity (one per flush, call or program chunk):

    flush     root: one flush, admission to resolution (server, pipeline)
    schedule  the scheduling step          resolve  one (query, part)
    fuse      fusing groups into families
    launch    the launch step              assemble  operands of a chunk
                                           dispatch  the program enqueue
    collect   the collection step          wait      device, per chunk
                                           copy      D2H, per chunk
                                           extract   host extraction

Two kinds of site.  ``with span(name):`` covers a stretch of code on one
thread; its parent is the thread's innermost open ``span``, or the handle
given as ``parent`` (how the collector thread attaches ``collect`` to its
flush).  ``flush()`` / ``end(handle)`` bracket a flush whose life crosses
tasks and threads; the handle is never the thread's current span, so the
next flush's spans on the same thread do not nest under it.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple

import jax


class Span(NamedTuple):
    id: int
    name: str
    t0_ns: int
    t1_ns: int
    parent: int
    flush: int


class _Open:
    """A span that has begun: its identity and its profiler annotation."""
    __slots__ = ("rec", "id", "name", "t0", "parent", "flush", "ann")

    def __init__(self, rec: "Recorder", name: str, parent: "_Open | None",
                 is_flush: bool):
        self.rec = rec
        self.id = next(rec.ids)
        self.name = name
        self.parent = parent.id if parent is not None else -1
        self.flush = (self.id if is_flush
                      else parent.flush if parent is not None else -1)
        self.ann = jax.profiler.TraceAnnotation("repro." + name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()

    def close(self):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(None, None, None)
        if self.rec is _rec:          # not after its recording stopped
            self.rec.spans.append(Span(self.id, self.name, self.t0, t1,
                                       self.parent, self.flush))


class Recorder:
    """The spans of one recording.  ``spans`` grows from the event-loop
    and collector threads alike: ``list.append`` and ``next`` on a count
    are atomic in CPython, and each thread keeps its own stack of open
    ``span`` sites."""

    def __init__(self):
        self.spans: list[Span] = []
        self.ids = itertools.count()
        self.local = threading.local()

    def stack(self) -> list:
        st = getattr(self.local, "stack", None)
        if st is None:
            st = self.local.stack = []
        return st


_rec: Recorder | None = None


class _Off:
    """The span of a site while recording is off: does nothing."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Site:
    __slots__ = ("rec", "name", "parent", "open")

    def __init__(self, rec: Recorder, name: str, parent):
        self.rec, self.name, self.parent = rec, name, parent

    def __enter__(self):
        st = self.rec.stack()
        parent = self.parent if self.parent is not None else (
            st[-1] if st else None)
        self.open = _Open(self.rec, self.name, parent, False)
        st.append(self.open)
        return self.open

    def __exit__(self, *exc):
        self.rec.stack().pop()
        self.open.close()
        return False


def span(name: str, parent: "_Open | None" = None):
    """Context manager for one span site (see the module docstring)."""
    if _rec is None:
        return _OFF
    return _Site(_rec, name, parent)


def flush() -> "_Open | None":
    """Begin a flush's root span; None while off.  Close it with ``end``."""
    if _rec is None:
        return None
    return _Open(_rec, "flush", None, True)


def end(handle: "_Open | None"):
    """Close a span begun by ``flush`` (nothing for None).  A span that
    closes after its recording stopped is not kept."""
    if handle is not None:
        handle.close()


def recording() -> bool:
    return _rec is not None


def start():
    """Begin recording into a fresh in-memory recorder."""
    global _rec
    _rec = Recorder()


def stop() -> list[Span]:
    """Stop recording; the spans closed while it ran, in closing order."""
    global _rec
    rec, _rec = _rec, None
    return rec.spans if rec is not None else []


def self_ns(spans: list[Span]) -> dict[int, int]:
    """Each span's self time: its duration less the part of its interval
    that its children cover (clipped to it, overlaps merged), so never
    below 0."""
    kids: dict[int, list] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append((s.t0_ns, s.t1_ns))
    out = {}
    for s in spans:
        covered, end_ = 0, s.t0_ns
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, end_), min(b, s.t1_ns)
            if b > a:
                covered += b - a
                end_ = b
        out[s.id] = s.t1_ns - s.t0_ns - covered
    return out


def totals_ns(spans: list[Span]) -> dict[str, int]:
    """Summed duration per span name."""
    out: dict[str, int] = {}
    for s in spans:
        out[s.name] = out.get(s.name, 0) + s.t1_ns - s.t0_ns
    return out


def self_totals_ns(spans: list[Span]) -> dict[str, int]:
    """Summed self time (``self_ns``) per span name."""
    own = self_ns(spans)
    out: dict[str, int] = {}
    for s in spans:
        out[s.name] = out.get(s.name, 0) + own[s.id]
    return out


def by_flush(spans: list[Span]) -> dict[int, dict[str, int]]:
    """Summed duration per span name within each flush, keyed by the
    flush's id (the id ``Request.flush`` holds); spans outside any flush
    are left out."""
    out: dict[int, dict[str, int]] = {}
    for s in spans:
        if s.flush >= 0:
            row = out.setdefault(s.flush, {})
            row[s.name] = row.get(s.name, 0) + s.t1_ns - s.t0_ns
    return out
