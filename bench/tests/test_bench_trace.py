"""The reduction from a profiler trace to busy and idle time, time per
device operation and named idle gaps."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from harness import devtrace

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_small_trace_busy_ops_and_gaps():
    trace = json.loads((FIXTURES / "trace_small.json").read_text())
    r = devtrace.reduce(trace, window_s=10e-6)
    # busy = [1200, 3400) U [4000, 5000) U [7200, 8000): 2200 + 1000 + 800
    assert r["busy_s"] == pytest.approx(4000e-9)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["op_s"] == pytest.approx(
        {"decoded_fold": 3000e-9, "fusion.1": 600e-9, "copy.3": 800e-9})
    assert r["device_ops"][0] == ["decoded_fold", pytest.approx(3000e-9)]
    # gaps [3400, 4000) under collect; [5000, 7200) under the 2nd schedule
    assert r["idle_gaps"] == [["schedule", pytest.approx(2200e-9)],
                              ["collect", pytest.approx(600e-9)]]


def test_union_merges_overlaps():
    evs = [["a", 0, 10], ["b", 5, 10], ["c", 15, 1], ["d", 20, 5]]
    assert devtrace.union(evs) == [(0, 16), (20, 25)]


def test_no_device_events_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.schedule", 0, 5]]}]}]}
    assert devtrace.reduce(trace, window_s=1.0) is None


def test_recorded_v5e_trace():
    """400 device ops recorded on one v5e chip; the expected numbers come
    from marking each op's nanoseconds on a 1 ns grid."""
    trace = json.loads((FIXTURES / "trace_v5e.json").read_text())
    r = devtrace.reduce(trace, window_s=2562991e-9)
    assert r["busy_s"] == pytest.approx(2517502e-9)
    assert r["device_ops"][0] == ["reshape.1", pytest.approx(575735e-9)]
    assert sum(r["op_s"].values()) >= r["busy_s"]
    # the three longest gaps fall inside the harness's launch span
    assert r["idle_gaps"][:3] == [["launch", pytest.approx(19463e-9)],
                                  ["launch", pytest.approx(13300e-9)],
                                  ["launch", pytest.approx(12241e-9)]]
    assert len(r["idle_gaps"]) == 10
