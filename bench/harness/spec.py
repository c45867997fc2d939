"""Find a cell's configuration, traffic mix and metric readers by the names
``BENCHMARK.json`` gives them: ``<file>`` of the configuration entry,
``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.py``.  Adding a
cell, a mix or a metric adds files and entries; nothing here changes."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]         # the cell's end-to-end metric entries
    per_layer: list[dict]          # the cell's per-layer metric entries


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / BENCH.name
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str, root: Path = ROOT):
    """The ``read(run) -> float | None`` function of one metric."""
    path = root / BENCH.name / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    table = json.loads((root / BENCH.name / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]
