"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``).  The run makes
the corpus and the query log from ``--seed``, builds and stages the index
through the program, warms the server up, offers the mix for ``--seconds``
and checks every answer against a plain numpy reference.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
it records a profiler trace of the window and reports the per-layer
metrics (``bench/metrics/<name>.py``).  The numbers compared with their
limits are the last lines on standard error; the result is the last line
on standard output.

It exits non-zero, printing no result, unless JAX finds a TPU with as
many chips as the cell asks for and the Pallas kernels run compiled.
"""

from __future__ import annotations

import os
import time


def _process_start() -> float:
    """perf_counter time at which this process started (Linux), so set-up
    counts interpreter start-up too."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return now - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return now


T_START = _process_start()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401  the program under test
        from harness import runner, spec
    except ImportError as e:
        print(f"[bench] FAIL: the program is not beside the benchmark: {e}",
              file=sys.stderr)
        return 1
    cell = spec.load_cell(args.workload)
    cache = runner.enable_compile_cache()
    runner.say(f"cell {cell.name}: config {cell.config_name}, traffic "
               f"{cell.traffic_name} {cell.traffic}, seed {args.seed}, "
               f"{args.seconds:g} s, trace {args.trace}, compile cache "
               f"{cache}")
    try:
        result = runner.run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), T_START)
    except runner.Refused as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
