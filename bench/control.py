"""Readings that set the limit of ``correct``, at a cell's own size.

For each seed, one set-up, then for each cell named (cells of one
configuration share the set-up) a window of the cell's own traffic and
three readings of the number ``correct`` compares (answers that differ
from the reference):

- program: the served answers (the lower reading; it must be 0);
- control: the reference with its longest fold skipped, put in the
  program's place over the same served queries (it must be above 0);
- altered_answer: a second window with ``collect_batch`` dropping the last
  doc of the first non-empty answer of every flush (a fault where answers
  are produced; it must be above 0).

    python3 bench/control.py --workloads <cell>[,<cell>] --seeds 11,12,13 \\
        --seconds 10

The benchmark's own runs never run this.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent),
                str(Path(__file__).resolve().parents[1] / "src")]


class _Answer:
    def __init__(self, docs, max_results: int):
        self.docs = docs[:max_results]
        self.count = int(docs.shape[0])


def served(win) -> list:
    return [(t.terms, t.req.result) for t in win.requests + win.after
            if t.ok]


def readings(su, cell, seconds: float) -> dict:
    from harness import reference, runner
    from repro.index import batch as batch_lib
    mr = su.server.max_results
    win, _, _ = runner.window(su, cell.traffic, seconds, None)
    good = served(win)
    orig = batch_lib.collect_batch

    def altered(pending):
        out = orig(pending)
        for res in out:
            if res.docs.shape[0]:
                res.docs = res.docs[:-1]
                break
        return out

    batch_lib.collect_batch = altered
    try:
        win2, _, _ = runner.window(su, cell.traffic, seconds, None)
    finally:
        batch_lib.collect_batch = orig
    bad = served(win2)
    ref = reference.Reference(su.corpus.postings)
    ctrl = reference.Reference(su.corpus.postings, drop_longest=True)
    ref.compute([q for q, _ in good + bad])
    ctrl.compute([q for q, _ in good])
    as_ctrl = [(q, _Answer(ctrl.answer(q), mr)) for q, _ in good]
    return {"cell": cell.name, "seed": su.seed, "answers": len(good),
            "program": len(reference.mismatches(good, ref, mr)),
            "control": len(reference.mismatches(as_ctrl, ref, mr)),
            "altered_answer": len(reference.mismatches(bad, ref, mr)),
            "altered_of": len(bad)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from harness import runner, spec
    cells = [spec.load_cell(w) for w in args.workloads.split(",")]
    if len({c.config_name for c in cells}) != 1:
        ap.error("the cells must share one configuration")
    runner.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            su = runner.setup(cells[0], seed)
        except runner.Refused as e:
            print(f"[control] FAIL: {e}", file=sys.stderr)
            return 1
        for cell in cells:
            print(json.dumps(readings(su, cell, args.seconds)), flush=True)
        del su
    return 0


if __name__ == "__main__":
    sys.exit(main())
