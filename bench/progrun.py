"""Run one benchmark cell once as ``bench/run.py`` does, with the
program's own span recorder on, and print the result as one JSON line.

    python3 bench/progrun.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is ``runner.run_cell`` unchanged: one set-up, one window, the
check against the reference.  Around the window the program's recorder
(``repro.trace``) runs, so ``--trace 0`` measures the window with the
recorder on and the profiler off.  With ``--trace 1`` the readers of the
program's spans (``SPAN_READERS``) report beside the cell's per-layer
metrics, from the spans and the profiler trace the run loads.  Either
way the line gains ``program``: each span's time per flush, the median
and the largest over the window's flushes (``progtrace.per_flush_ms``),
and the answered requests' latency split into the wait before their
flush and that flush's stages (``progtrace.latency_split_ms``).

``bench/run.py`` does not record the program's spans: ``runner.py``
would need the hook PERF.md §7 describes.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys

import run as bench_run   # first: sets the process start and sys.path

from harness import devtrace, progtrace, runner, spec  # noqa: E402

# readers of the program's spans, with their units, that BENCHMARK.json
# does not list yet
SPAN_READERS = {"resolve_ms_per_query": "ms",
                "assemble_ms_per_flush": "ms",
                "dispatch_ms_per_flush": "ms",
                "collect_host_ms_per_flush": "ms",
                "idle_host_bound_share": "%"}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """``runner.run_cell`` with the recorder around its window and
    ``run.program`` filled; the result gains ``program``."""
    held: dict = {}
    window, load, Run = runner.window, devtrace.load, runner.Run

    def recorded_window(su, mix, secs, trace_dir):
        with progtrace.Recording() as rec:
            out = window(su, mix, secs, trace_dir)
        held.update(spans=rec.spans, timed=out[0].requests)
        return out

    def kept_load(path):
        held["trace"] = load(path)
        return held["trace"]

    class ProgramRun(Run):
        @functools.cached_property
        def program(self):
            # read first by a reader, after the window and the trace load
            return progtrace.program(held.get("spans"), held.get("trace"))

    have = {m["name"] for m in cell.per_layer}
    cell.per_layer = cell.per_layer + [
        {"name": n, "unit": u} for n, u in SPAN_READERS.items()
        if n not in have]
    runner.window, devtrace.load, runner.Run = (recorded_window, kept_load,
                                                ProgramRun)
    try:
        result = runner.run_cell(cell, seed, seconds, trace, t_start,
                                 require_tpu)
    finally:
        runner.window, devtrace.load, runner.Run = window, load, Run
    if held.get("spans") is not None:
        spans = progtrace.rows(held["spans"])
        result["program"] = {
            "per_flush_ms": progtrace.per_flush_ms(spans),
            "latency_split_ms": progtrace.latency_split_ms(
                spans, held["timed"])}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cache = runner.enable_compile_cache()
    runner.say(f"cell {cell.name} with the program's recorder on, seed "
               f"{args.seed}, {args.seconds:g} s, trace {args.trace}, "
               f"compile cache {cache}")
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          bench_run.T_START)
    except runner.Refused as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
