"""Double-buffered pipelined serving (DESIGN.md §2.8).

``batch.execute_batch`` is host→device serialized: the host schedules and
stacks a batch, dispatches its device programs, then *blocks* materializing
the results before it even looks at the next batch — so the device idles
while the host schedules, and the host idles while the device executes.
This module overlaps the two, the same way the paper overlaps decoding with
intersection: JAX dispatch is asynchronous, so once batch k's programs are
enqueued the host can immediately schedule and dispatch batch k+1 (and
k+2, … up to ``depth``) while the device chews through k.  ``depth`` bounds
the number of un-collected batches in flight — each one pins its operand
and result buffers, so depth is a memory knob, not just a latency knob:

    depth 1   launch → collect, strictly serial (== execute_batch)
    depth 2   classic double buffering: stage k+1 while k executes
    depth d   d-1 batches of slack for jittery schedule times

The pipeline composes with the device-resident index: with a
``source.ResidentPool`` the host stage is pure bookkeeping (bucketing +
skip-index searches + gathers of resident rows), which is exactly what lets
it hide under device execution.  Mutating shared state (pool staging, cache
fills, layout memo) happens in schedule order, so results are byte-identical
to ``execute_batch`` run batch by batch — the differential guarantee
``tests/test_pipeline.py`` locks in across depths, backends, and corpora.

Each batch is one ``flush`` span of ``repro.trace`` (when it records),
parent of the batch's ``schedule``, ``launch`` and ``collect`` spans:

    schedule  host scheduling: resolve/bucketing + candidate-block search
              (+ megagroup fusion, which is pure bookkeeping)
    assemble  operand assembly (arena gathers / host stacking + upload),
              inside ``launch``, recorded by the launcher
    dispatch  async program enqueue, likewise
    collect   blocked on device results, the D2H copy and extraction

``serve.py --pipeline N`` and ``bench_engine.py --profile`` report the
breakdown; ``collect`` collapsing toward the extraction alone at depth ≥ 2
is the visible signature of a hidden device.

This module is DESIGN.md §2.8 (the pipelined half); the sharded executor
(DESIGN.md §2.9, ``repro.index.shard``) reuses this exact loop through the
``schedule_fn``/``launch_fn`` hooks, fanning each launch across the shard
devices while in-flight tracking, depth bounding, and the stage spans
stay shared.  Invariants callers rely on:

  * **Byte-identical to the unpipelined path** — mutations of shared
    state (pool staging, cache fills, layout memo, arena growth,
    fusion-plan ceilings) happen in schedule order, so results equal
    ``execute_batch`` run chunk by chunk, and therefore ``engine.query``
    per query, at every depth.
  * **Depth bounds memory** — at most ``depth`` un-collected batches pin
    operand/result buffers; depth 1 is strictly serial.
  * **Collect order is submission order** — results return in query
    order regardless of which device finished first.
"""

from __future__ import annotations

from collections import deque

from repro import trace
from repro.index import batch as batch_lib
from repro.index.builder import HybridIndex
from repro.index.engine import QueryResult


def execute_pipelined(index: HybridIndex, queries: list[list[int]], *,
                      batch_size: int, depth: int = 2,
                      backend: str = "jax", max_results: int = 1 << 16,
                      max_group_size: int = batch_lib.MAX_GROUP_SIZE,
                      cache=None, skip: bool = True, pool=None,
                      fuse: bool = True,
                      plan: "batch_lib.FusionPlan | None" = None,
                      stats: dict | None = None,
                      schedule_fn=None, launch_fn=None
                      ) -> list[QueryResult]:
    """Answer ``queries`` in ``batch_size`` chunks with up to ``depth``
    batches in flight; results are byte-identical to ``execute_batch`` run
    chunk by chunk (and therefore to ``engine.query`` per query).

    ``fuse``/``plan`` mirror ``execute_batch``: each chunk's scheduled
    groups coarsen into megagroup families before launch (DESIGN.md
    §2.10).  A single sticky plan is created for the whole run when none
    is passed, so fused signatures converge across chunks.

    ``schedule_fn(chunk, stats) -> groups`` and ``launch_fn(groups,
    n_queries, stats) -> PendingBatch`` override the two pipeline stages —
    the sharded executor (``repro.index.shard``, DESIGN.md §2.9) plugs in
    per-shard group assembly and fan-out dispatch here while reusing this
    loop's in-flight tracking and stage spans unchanged.  Defaults are
    the single-device ``batch`` scheduler/launcher."""
    assert depth >= 1, depth
    assert batch_size >= 1, batch_size
    if fuse and plan is None:
        plan = batch_lib.FusionPlan()
    if schedule_fn is None:
        def schedule_fn(chunk, stats):
            groups = batch_lib.schedule(index, chunk, cache=cache,
                                        skip=skip, stats=stats, pool=pool)
            if fuse:
                groups = batch_lib.fuse_groups(groups, plan=plan,
                                               stats=stats)
            return groups
    if launch_fn is None:
        def launch_fn(groups, n_queries, stats):
            return batch_lib.launch_groups(
                groups, n_queries=n_queries, backend=backend,
                max_results=max_results, max_group_size=max_group_size,
                pool=pool, stats=stats)
    inflight: deque[batch_lib.PendingBatch] = deque()
    out: list[QueryResult] = []

    def drain_one():
        pending = inflight.popleft()
        out.extend(batch_lib.collect_batch(pending))
        trace.end(pending.flush)

    for lo in range(0, len(queries), batch_size):
        chunk = queries[lo: lo + batch_size]
        flush = trace.flush()
        with trace.span("schedule", parent=flush):
            groups = schedule_fn(chunk, stats)
        with trace.span("launch", parent=flush):
            pending = launch_fn(groups, len(chunk), stats)
        pending.flush = flush
        inflight.append(pending)
        while len(inflight) >= depth:
            drain_one()
    while inflight:
        drain_one()
    return out
