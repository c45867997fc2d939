"""Cells, mixes and metric readers are found by file name, and
BENCHMARK.json keeps the shape its readers rely on: names, units, bounds,
and the cells each per-layer metric reports in."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from harness import runner, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", [cell])
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"]) <= set(cfg)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_reports(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    assert c.config["n_docs"] > 0 and c.traffic["kind"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric))


def test_new_cell_mix_and_metric_need_only_new_files(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "mq-bursty.json").write_text(
        json.dumps({"kind": "bursty", "rate_qps": 5.0, "burst": 8}))
    (tmp_path / "bench" / "metrics" / "answered.py").write_text(
        "def read(run):\n    return run.served()\n")
    bench["workloads"].append(
        {"name": "clueweb09b-decoded.mq-bursty",
         "config": "clueweb09b-decoded", "traffic": "mq-bursty",
         "chips": 1, "why": "bursts"})
    bench["per_layer"].append(
        {"name": "answered", "unit": "q", "better": "higher",
         "source": "program_counter", "layer": "admission and flush",
         "moves": "p50_ms", "workloads": ["clueweb09b-decoded.mq-bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("clueweb09b-decoded.mq-bursty", tmp_path)
    assert cell.traffic["kind"] == "bursty"
    assert [m["name"] for m in cell.per_layer] == ["answered"]
    assert spec.reader("answered", tmp_path)(
        type("R", (), {"served": lambda self: 7})()) == 7
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", tmp_path)


def test_peaks_table_and_unknown_device():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_readers_read_nothing_from_an_empty_run():
    from harness import traffic
    run = runner.Run(cell="x", seconds=1.0, setup_s=2.0, loop="closed",
                     max_batch=32, window=traffic.Window([], 0.0, 1.0),
                     n_flushes=0, counters={}, compiles=0, peak_bytes=None,
                     postings=0, peaks=None)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        value = spec.reader(m["name"])(run)
        if m["name"] == "setup_s":
            assert value == 2.0
        elif m["name"] == "window_compiles":
            assert value == 0
        else:
            assert value is None, m["name"]
