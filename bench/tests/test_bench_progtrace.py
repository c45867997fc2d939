"""The program's spans as the benchmark reads them: the device idle they
explain in a small recorded trace, and each reader of the program's spans
and counters on a hand-built run."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import devtrace, progtrace, runner, spec, traffic

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_host_bound_idle_in_a_small_trace():
    trace = json.loads((FIXTURES / "trace_program.json").read_text())
    # busy = [1000, 2000) U [3000, 3500) U [6000, 8000): 3500 of 9000 ns
    assert devtrace.reduce(trace, 9e-6)["busy_s"] == pytest.approx(3500e-9)
    # host work: schedule [0, 1500) (the copy [500, 800) inside it counts
    # once), assemble [1500, 2500), dispatch [2500, 2600), copy
    # [3500, 4000), extract [4000, 4500) and [7000, 7500); flush, resolve,
    # launch, collect, wait and bench.* spans are not host work
    assert progtrace.host_work(trace) == [(0, 2600), (3500, 4500),
                                          (7000, 7500)]
    # idle inside it: [0, 1000) + [2000, 2600) + [3500, 4500) = 2600 ns;
    # [2600, 3000) sits under launch alone, [4500, 6000) under collect
    # alone: idle, but not host work
    assert progtrace.host_bound_idle_s(trace) == pytest.approx(2600e-9)


def test_no_device_op_explains_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "loop", "events": [["repro.schedule", 0, 5]]}]}]}
    assert progtrace.host_bound_idle_s(trace) is None


def test_recording_keeps_the_program_spans():
    from repro import trace
    with progtrace.Recording() as rec:
        fl = trace.flush()
        with trace.span("schedule", parent=fl):
            pass
        trace.end(fl)
    assert [s[1] for s in rec.spans] == ["schedule", "flush"]
    assert not trace.recording()
    assert progtrace.program(rec.spans, None) == {
        "spans": rec.spans, "host_bound_idle_s": None}


# (id, name, t0_ns, t1_ns, parent, flush): two flushes, in ms
_SPANS = [
    (0, "flush", 0, 10_000_000, -1, 0),
    (1, "schedule", 0, 4_000_000, 0, 0),
    (2, "resolve", 0, 1_000_000, 1, 0),
    (3, "resolve", 1_000_000, 3_000_000, 1, 0),
    (4, "launch", 4_000_000, 8_000_000, 0, 0),
    (5, "assemble", 4_000_000, 7_000_000, 4, 0),
    (6, "dispatch", 6_000_000, 7_000_000, 5, 0),   # sharded: in assemble
    (7, "dispatch", 7_000_000, 8_000_000, 4, 0),
    (8, "collect", 8_000_000, 10_000_000, 0, 0),
    (9, "copy", 8_000_000, 8_500_000, 8, 0),
    (10, "extract", 8_500_000, 10_000_000, 8, 0),
    (11, "flush", 10_000_000, 12_000_000, -1, 11),
    (12, "assemble", 10_000_000, 11_000_000, 11, 11),
]


def _run(program=None, counters=None, trace=None, served=4, flushes=2):
    done = SimpleNamespace(outcome="done")
    reqs = [traffic.Timed([1], 0.0, done) for _ in range(served)]
    run = runner.Run(cell="x", seconds=1.0, setup_s=1.0, loop="closed",
                     max_batch=32, window=traffic.Window(reqs, 0.0, 1.0),
                     n_flushes=flushes, counters=counters or {}, compiles=0,
                     peak_bytes=None, postings=0, peaks=None, trace=trace)
    if program is not None:
        run.program = program
    return run


@pytest.mark.parametrize("metric,value", [
    # resolve 1 + 2 ms over 4 answers
    ("resolve_ms_per_query", 3 / 4),
    # self time: 3 ms less the 1 ms dispatch inside it, plus 1 ms; 2 flushes
    ("assemble_ms_per_flush", (2 + 1) / 2),
    ("dispatch_ms_per_flush", (1 + 1) / 2),
    ("collect_host_ms_per_flush", (0.5 + 1.5) / 2),
    # 0.25 s of 2 s
    ("idle_host_bound_share", 12.5),
    ("probe_slot_fill", 100 * 34 / 768),
    ("d2h_bytes_per_query", 4096 / 4),
])
def test_readers_on_a_hand_built_run(metric, value):
    run = _run(program={"spans": _SPANS, "host_bound_idle_s": 0.25},
               counters={"probe_slots": 768, "probe_slots_useful": 34,
                         "d2h_bytes": 4096},
               trace={"window_s": 2.0, "busy_s": 1.5})
    assert spec.reader(metric)(run) == pytest.approx(value)
    # a program without the recorder or the counters: nothing to read
    assert spec.reader(metric)(_run(trace={"window_s": 2.0})) is None


def test_per_flush_groups_by_the_flush_id():
    got = progtrace.per_flush_ms(progtrace.rows(_SPANS))
    # flush 0 holds every span; flush 11 only its root and one assemble
    assert got["flush"] == pytest.approx((6.0, 10.0))       # 10 and 2 ms
    assert got["assemble"] == pytest.approx((2.0, 3.0))     # 3 and 1 ms
    assert got["resolve"] == pytest.approx((3.0, 3.0))      # 1 + 2 ms
    assert got["dispatch"] == pytest.approx((2.0, 2.0))     # 1 + 1 ms


def test_latency_split_joins_requests_to_their_flush():
    def timed(due, flush, outcome="done"):
        req = SimpleNamespace(outcome=outcome, flush=flush)
        return traffic.Timed([1], due, req)

    reqs = [timed(-0.001, 0),      # due 1 ms before flush 0 starts at 0
            timed(0.007, 11),      # due 3 ms before flush 11 starts
            timed(0.0, -1),        # carried by no recorded flush
            timed(0.0, 0, "timeout")]
    got = progtrace.latency_split_ms(progtrace.rows(_SPANS), reqs)
    # flush 0: schedule 4, launch 4, collect 2, whole 10 ms; flush 11
    # has no stage but its 2 ms root
    assert got == pytest.approx({"queue": 2.0, "schedule": 2.0,
                                 "launch": 2.0, "collect": 1.0,
                                 "flush": 6.0})
    assert progtrace.latency_split_ms(progtrace.rows(_SPANS),
                                      reqs[2:]) is None


@pytest.mark.parametrize("trace", [False, True])
def test_progrun_reads_the_program_in_a_tiny_run(tiny_root, monkeypatch,
                                                 trace):
    import progrun
    from repro.index import batch as batch_lib
    monkeypatch.setattr(batch_lib, "PALLAS_MIN_OCCUPANCY", 0.0)
    c = spec.load_cell("clueweb09b-decoded.mq-poisson", tiny_root)
    c.traffic = {**c.traffic, "rate_qps": 50.0}
    window, run_cls = runner.window, runner.Run
    r = progrun.run_cell(c, seed=2**31 + 777, seconds=0.6, trace=trace,
                         t_start=0.0, require_tpu=False)
    assert (runner.window, runner.Run) == (window, run_cls)
    assert r["correct"] is True
    per_flush = r["program"]["per_flush_ms"]
    assert {"flush", "schedule", "resolve", "launch", "assemble",
            "dispatch", "collect", "copy", "extract"} <= set(per_flush)
    split = r["program"]["latency_split_ms"]
    assert list(split) == ["queue", "schedule", "launch", "collect",
                           "flush"]
    assert all(v >= 0 for v in split.values())
    spans_read = {"resolve_ms_per_query", "assemble_ms_per_flush",
                  "dispatch_ms_per_flush", "collect_host_ms_per_flush"}
    if trace:
        # a CPU trace holds no TPU op: idle_host_bound_share reads nothing
        assert spans_read <= set(r["metrics"])
        assert "idle_host_bound_share" not in r["metrics"]
    else:
        assert not spans_read & set(r["metrics"])
