"""Shared fixtures of the benchmark's own tests: the harness on the path,
and a copy of the benchmark whose configurations are cut to a size a CPU
test run can hold (2**16 docs, a 48-query log)."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

TINY = {"n_docs": 1 << 16, "log_queries": 48}


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    """A checkout holding BENCHMARK.json and the benchmark's files, with
    every configuration cut to ``TINY``."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for cfg in (tmp_path / BENCH.name / "configs").glob("*.json"):
        data = json.loads(cfg.read_text())
        data.update(TINY)
        cfg.write_text(json.dumps(data))
    return tmp_path
