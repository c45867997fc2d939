"""Reduce a JAX profiler trace to device busy and idle time, time per
device operation, and idle gaps named by what the host was doing.

``load`` turns the ``.xplane.pb`` the profiler writes into plain data:
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``.  Everything else works on that form, so a small
recorded trace kept as JSON (``bench/tests/fixtures``) checks the same
arithmetic every run uses.

Device events are those on a ``/device:TPU:<n>`` plane (not its
SparseCore planes), line ``XLA Ops``; busy time is the union of their
intervals.  Host spans are the harness's ``TraceAnnotation`` events, named
``bench.<layer>``, on any host plane.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
NAME_CHARS = 160
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return {"planes": [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [[ev.name, float(ev.start_ns),
                                float(ev.duration_ns)]
                               for ev in line.events]}
                   for line in plane.lines]}
        for plane in data.planes]}


def device_events(trace: dict) -> dict[str, list]:
    """Per device plane, its op events sorted by start."""
    out = {}
    for plane in trace["planes"]:
        if not _DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == _OPS_LINE:
                out[plane["name"]] = sorted(line["events"],
                                            key=lambda e: e[1])
    return out


def host_spans(trace: dict) -> list:
    """The harness's own spans: [layer, start_ns, end_ns]."""
    spans = []
    for plane in trace["planes"]:
        if _DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append([name[len(SPAN_PREFIX):], start,
                                  start + dur])
    return sorted(spans, key=lambda s: s[1])


def union(events: list) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of events sorted by start."""
    merged: list[list[float]] = []
    for _, start, dur in events:
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def reduce(trace: dict, window_s: float, top: int = 10) -> dict | None:
    """Busy seconds (mean over device planes), seconds per op name summed
    over planes, and the longest idle gaps between busy intervals, each
    named by the host span that overlaps it most (``idle`` if none).
    ``None`` when the trace holds no device operation."""
    per_dev = {d: evs for d, evs in device_events(trace).items() if evs}
    if not per_dev:
        return None
    busy_ns = 0.0
    op_ns: dict[str, float] = {}
    gaps = []
    for evs in per_dev.values():
        busy = union(evs)
        busy_ns += sum(b - a for a, b in busy)
        for name, _, dur in evs:
            op_ns[name] = op_ns.get(name, 0.0) + dur
        gaps += [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    spans = host_spans(trace)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        best, overlap = "idle", 0.0
        for layer, s, e in spans:
            o = min(b, e) - max(a, s)
            if o > overlap:
                best, overlap = layer, o
        named.append([best, (b - a) * 1e-9])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    # an op's name is its HLO text; its head (name, result type) is enough
    ops = [(k[:NAME_CHARS], v) for k, v in ops]
    return {"busy_s": busy_ns * 1e-9 / len(per_dev),
            "window_s": window_s,
            "op_s": {k: v * 1e-9 for k, v in op_ns.items()},
            "device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": named}
