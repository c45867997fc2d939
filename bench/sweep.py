"""Find the knee of an open-loop cell: the highest Poisson rate at which the
backlog does not grow over the window.  One set-up, then a closed-loop
window to measure saturated q/s, then one Poisson window per fraction of
it.  The rate a mix file states comes from this sweep, run once on the
chip; the benchmark's own runs never search.

    python3 bench/sweep.py --workload <open-loop cell> --seed <n> \\
        --seconds 15 --fractions 0.5,0.7,0.8,0.9,1.0 [--clients 64]

Prints one JSON line per window.  A window's backlog is the number of
requests due but not yet answered, sampled at each due time; it grows
when its mean over the last quarter of the window is more than twice the
first quarter's and more than 4 requests above it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent),
                str(Path(__file__).resolve().parents[1] / "src")]


def backlog(win) -> list[int]:
    import numpy as np
    due = np.array([t.due for t in win.requests])
    done = np.array([t.req.t_done if t.ok else np.inf for t in win.requests])
    ends = np.sort(done)
    # answered by each due time, among requests due by then
    return [int(i + 1 - np.searchsorted(ends, d, side="right"))
            for i, d in enumerate(due)]


def summary(win) -> dict:
    import numpy as np
    from harness.runner import Run
    lat = [(t.req.t_done - t.due) * 1e3 if t.ok else float("inf")
           for t in win.requests]
    done = [t.req.t_done for t in win.requests if t.ok]
    b = backlog(win)
    q = max(len(b) // 4, 1)
    first, last = float(np.mean(b[:q])), float(np.mean(b[-q:]))
    return {"requests": len(win.requests), "answered": len(done),
            "answered_per_s": len(done) / (max(done) - win.t0),
            "p50_ms": Run.pctl(lat, 50), "p95_ms": Run.pctl(lat, 95),
            "backlog_first_quarter": first, "backlog_last_quarter": last,
            "backlog_grows": last > 2 * first and last - first > 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0")
    ap.add_argument("--clients", type=int, default=64)
    args = ap.parse_args(argv)
    from harness import runner, spec
    cell = spec.load_cell(args.workload)
    runner.enable_compile_cache()
    try:
        su = runner.setup(cell, args.seed)
    except runner.Refused as e:
        print(f"[sweep] FAIL: {e}", file=sys.stderr)
        return 1
    win, _, _ = runner.window(su, {"kind": "closed",
                                   "clients": args.clients},
                              args.seconds, None)
    done = [t.req.t_done for t in win.requests
            if t.ok and t.req.t_done <= win.t_end]
    sat = len(done) / (max(done) - win.t0)
    print(json.dumps({"closed_clients": args.clients,
                      "qps": sat, "compiles": su.counter.n}), flush=True)
    for f in (float(x) for x in args.fractions.split(",")):
        rate = round(f * sat, 2)
        win, _, _ = runner.window(su, {"kind": "poisson", "rate_qps": rate},
                                  args.seconds, None)
        print(json.dumps({"fraction": f, "rate_qps": rate,
                          "compiles": su.counter.n,
                          **summary(win)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
