"""bench/run.py refuses a platform that is not a TPU, and a whole run
(the chip check skipped, the sizes cut) decides ``correct`` by the
reference: true when the timed path is sound, false when an answer is
altered where the program produces it."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import runner, spec

RUN = spec.BENCH / "run.py"


def test_run_refuses_a_cpu_platform():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("REPRO_PALLAS_INTERPRET", None)
    p = subprocess.run(
        [sys.executable, str(RUN), "--workload",
         "clueweb09b-decoded.mq-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=spec.ROOT, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_device_check_refuses_cpu_in_process():
    with pytest.raises(runner.Refused, match="no TPU"):
        runner.check_device(1, require_tpu=True)


def _alter_one_answer(monkeypatch):
    """A fault where answers are produced: ``collect_batch`` drops the
    last doc of the first non-empty answer of every flush (warm-up's
    launches too, whose answers nothing reads)."""
    from repro.index import batch as batch_lib
    orig = batch_lib.collect_batch

    def broken(pending):
        out = orig(pending)
        for res in out:
            if res.docs.shape[0]:
                res.docs = res.docs[:-1]
                break
        return out

    monkeypatch.setattr(batch_lib, "collect_batch", broken)


@pytest.mark.parametrize("fault", [None, "altered_answer"])
@pytest.mark.parametrize("cell", ["clueweb09b-decoded.mq-closed",
                                  "clueweb09b-uncached.mq-closed",
                                  "clueweb09b-decoded.mq-poisson"])
def test_tiny_run_decides_correct(tiny_root, monkeypatch, cell, fault):
    from repro.index import batch as batch_lib
    # interpret-mode kernels on the CPU: keep every fold in the kernel path
    monkeypatch.setattr(batch_lib, "PALLAS_MIN_OCCUPANCY", 0.0)
    c = spec.load_cell(cell, tiny_root)
    if c.traffic["kind"] != "closed":
        c.traffic = {**c.traffic, "rate_qps": 50.0}
    if fault:
        _alter_one_answer(monkeypatch)
    r = runner.run_cell(c, seed=2**31 + 12345, seconds=0.6, trace=False,
                        t_start=0.0, require_tpu=False)
    json.dumps(r)
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) <= {m["name"] for m in c.end_to_end}
    assert "setup_s" in r["metrics"]
    bad = r["checks"]["mismatched_answers"]
    if fault:
        assert r["correct"] is False and bad["value"] > 0
    else:
        assert r["correct"] is True and bad == {"value": 0, "limit": 0}
    assert np.isfinite(r["metrics"]["setup_s"]["value"])
