"""The program's own spans in a traced window, and the device idle they
explain.

The program records spans inside its served path (``repro.trace``): one
``flush`` root per flush, with ``schedule`` (``resolve``, ``fuse``),
``launch`` (``assemble``, ``dispatch``) and ``collect`` (``wait``,
``copy``, ``extract``) below it.  While it records, each span is also a
profiler annotation named ``repro.<span>``, on the device trace's clock.

- ``Recording``: around a traced window, starts and stops that recorder
  and keeps its spans as plain tuples ``(id, name, t0_ns, t1_ns, parent,
  flush)``.  A program without the recorder records nothing.
- ``host_bound_idle_s``: from a loaded profiler trace (``devtrace.load``),
  the time in which no device op runs while one of the program's host-work
  annotations (``HOST_WORK``) is open: the idle the host causes, as
  against idle with no work in hand.
- ``program``: what the readers of the program's spans see, as
  ``run.program``; the span readers return None where a run has none.
- ``rows`` and ``spans_of``: spans as ``repro.trace.Span`` rows, whose
  sums and self times ``repro.trace`` computes.
- ``per_flush_ms`` and ``latency_split_ms``: each span's time per flush,
  grouped by the spans' flush id, and each answered request's latency
  split into its wait before its flush and that flush's stages, joined
  on ``Request.flush``.

``runner`` does not call these yet: the readers of ``run.program``
report once ``runner.window`` enters a ``Recording`` inside the profiler
session and ``run_cell`` sets ``run.program = progtrace.program(rec.spans,
loaded)`` from the trace it loads (PERF.md §7).  ``bench/progrun.py``
runs a cell that way.
"""

from __future__ import annotations

import statistics

from harness import devtrace

PREFIX = "repro."
HOST_WORK = ("schedule", "assemble", "dispatch", "copy", "extract")


class Recording:
    """Context manager: the program's span recorder on for the block."""

    def __init__(self):
        try:
            from repro import trace
        except ImportError:         # a program without the recorder
            trace = None
        self._trace = trace
        self.spans: list[tuple] | None = None

    def __enter__(self):
        if self._trace is not None:
            self._trace.start()
        return self

    def __exit__(self, *exc):
        if self._trace is not None:
            self.spans = [tuple(s) for s in self._trace.stop()]
        return False


def _merged(intervals) -> list[tuple[float, float]]:
    return devtrace.union(sorted(([None, a, b - a] for a, b in intervals),
                                 key=lambda e: e[1]))


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _overlap(xs: list, ys: list) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def host_work(trace: dict) -> list[tuple[float, float]]:
    """Merged intervals (ns) in which a ``HOST_WORK`` annotation of the
    program is open, on any host plane."""
    names = {PREFIX + n for n in HOST_WORK}
    spans = []
    for plane in trace["planes"]:
        if devtrace._DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans += [(s, s + d) for name, s, d in line["events"]
                      if name in names]
    return _merged(spans)


def host_bound_idle_s(trace: dict) -> float | None:
    """Seconds of host work during which the device runs no op, the mean
    over device planes; None when the trace holds no device op."""
    per_dev = [evs for evs in devtrace.device_events(trace).values() if evs]
    if not per_dev:
        return None
    work = host_work(trace)
    idle = [_length(work) - _overlap(work, devtrace.union(evs))
            for evs in per_dev]
    return sum(idle) * 1e-9 / len(per_dev)


def program(spans: list | None, trace: dict | None) -> dict | None:
    """``run.program``: the window's spans and the host-bound idle."""
    if spans is None:
        return None
    return {"spans": spans,
            "host_bound_idle_s": (host_bound_idle_s(trace)
                                  if trace is not None else None)}


def rows(spans: list) -> list:
    """Recorded span tuples as ``repro.trace.Span`` rows."""
    from repro import trace
    return [trace.Span(*s) for s in spans]


def spans_of(run) -> list | None:
    """The program's spans of a run as ``repro.trace.Span`` rows; None
    where it recorded none."""
    prog = getattr(run, "program", None)
    return rows(prog["spans"]) if prog else None


def per_flush_ms(spans: list) -> dict[str, tuple[float, float]]:
    """Per span name, the median and the largest of its summed time in
    one flush (ms), over the flushes that hold it."""
    from repro import trace
    rows: dict[str, list] = {}
    for row in trace.by_flush(spans).values():
        for name, ns in row.items():
            rows.setdefault(name, []).append(ns * 1e-6)
    return {name: (statistics.median(v), max(v))
            for name, v in sorted(rows.items())}


STAGES = ("schedule", "launch", "collect", "flush")


def latency_split_ms(spans: list, timed: list) -> dict[str, float] | None:
    """Medians over the answered requests that name a recorded flush
    (``Request.flush``): ``queue``, from the request's due time to its
    flush's start, and the time of each of that flush's ``STAGES`` (the
    whole ``flush`` root last).  None when no request joins a flush."""
    from repro import trace
    start = {s.id: s.t0_ns for s in spans if s.name == "flush"}
    rows = trace.by_flush(spans)
    parts: dict[str, list] = {k: [] for k in ("queue",) + STAGES}
    for t in timed:
        f = t.req.flush if t.ok else -1
        if f not in start:
            continue
        parts["queue"].append(start[f] * 1e-6 - t.due * 1e3)
        for k in STAGES:
            parts[k].append(rows[f].get(k, 0) * 1e-6)
    if not parts["queue"]:
        return None
    return {k: statistics.median(v) for k, v in parts.items()}
