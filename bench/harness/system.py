"""The system under test, built from a configuration: the program's index
builder, its resident pool and its continuous-batching server.  This is the
only module that imports the program (``repro``), and it touches only what
the server itself drives: ``builder.build``, ``source.ResidentPool``,
``ContinuousBatchingServer`` with its dispatch seam (``_schedule``,
``_launch``) and ``batch.collect_batch``, and the ``stats`` counters.
"""

from __future__ import annotations

import time
from collections import defaultdict

import jax

SPAN_LAYERS = ("schedule", "launch", "collect")


def build(cfg: dict, corpus, say=print) -> tuple:
    """(server, index) for a configuration over a corpus."""
    from repro.index import builder, source
    from repro.launch import server as server_lib
    ix = cfg["index"]
    t = time.perf_counter()
    index = builder.build(corpus.postings, corpus.n_docs,
                          codec_name=ix["codec"], B=ix["bitmap_max_gap"],
                          n_parts=ix["n_parts"])
    say(f"index built in {time.perf_counter() - t:.2f} s: "
        f"{index.stats()['bytes_per_int']:.3f} B/posting stored")
    pool = None
    if cfg["resident"]:
        t = time.perf_counter()
        pool = source.ResidentPool()
        pool.warm(index)
        say(f"pool staged in {time.perf_counter() - t:.2f} s: "
            f"{pool.stats()['resident_ints']:,} ints resident")
    s = cfg["server"]
    server = server_lib.ContinuousBatchingServer(
        index, backend=s["backend"], fuse=s["fuse"],
        max_batch=s["max_batch"], max_wait_ms=s["max_wait_ms"],
        depth=s["depth"], max_queue=s["max_queue"],
        max_results=s["max_results"], pool=pool)
    return server, index


def _ladder(limit: int) -> list[int]:
    """The batch-row bucket sizes up to ``limit`` rows: 1, 2, 3, 4, 6, 9,
    13, ... (each about 1.5 times the last), then ``limit`` itself."""
    sizes, b = [], 1
    while b < limit:
        sizes.append(b)
        b = b * 3 // 2 if b >= 2 else b + 1
    return sizes + [limit]


def _state(server) -> tuple:
    plan = server.plan
    dims = sorted(plan.dims.items()) if plan is not None else ()
    pool = server.pool
    return (repr(dims), pool.staged_lists if pool is not None else 0)


def _distinct(log: list) -> list:
    """The log's distinct term sets, first occurrence order."""
    seen, out = set(), []
    for q in log:
        key = tuple(sorted(q))
        if key not in seen:
            seen.add(key)
            out.append(q)
    return out


def _roles(index, q) -> set:
    """(part, role, term) for one query: in each part where no term is
    empty, its shortest list term is the seed, its other list terms are
    folds and its bitmap terms are probes (the index's own ``kind`` and
    ``n``, ties in query order)."""
    out = set()
    for pi, part in enumerate(index.parts):
        tps = [(t, part.terms[t]) for t in q]
        if any(tp.kind == "empty" for _, tp in tps):
            continue
        lists = sorted(((t, tp) for t, tp in tps if tp.kind == "list"),
                       key=lambda x: x[1].n)
        for i, (t, _) in enumerate(lists):
            out.add((pi, "seed" if i == 0 else "fold", t))
        out |= {(pi, "bitmap", t) for t, tp in tps if tp.kind == "bitmap"}
    return out


def cover(index, log: list) -> list:
    """A few queries of the log that together give every list of every
    part each role some query of the log gives it (greedy set cover)."""
    todo = {tuple(q): _roles(index, q) for q in _distinct(log)}
    left = set().union(*todo.values()) if todo else set()
    picked = []
    while left:
        q = max(todo, key=lambda k: len(todo[k] & left))
        picked.append(list(q))
        left -= todo.pop(q)
    return picked


def warm(server, index, log: list, n_parts: int, say=print) -> dict:
    """Bring the server to its steady state for this log, so the window
    compiles nothing and stages nothing, without executing the log once
    per batch size.

    1. Schedule the log's distinct queries, max-batch chunks, without
       launching, until neither the fused plan's ceilings nor the pool's
       residency change: every family reaches the ceiling any window
       flush can raise it to.
    2. With a resident pool, serve a cover of the log (every list in every
       role the log gives it) at max batch, so the pool's row arenas hold
       every row a window flush can gather.
    3. For each fused family the log forms, launch its rows in every
       batch-row bucket a flush can land in (a flush holds up to
       ``max_batch * n_parts`` rows of one family), smallest first, until
       a launch splits into several programs: past that, every flush is
       made of buckets already compiled.
    """
    from repro.index import batch as batch_lib
    t0 = time.perf_counter()
    stats: dict = {}
    mb = server.max_batch
    distinct = _distinct(log)
    chunks = [distinct[i:i + mb] for i in range(0, len(distinct), mb)]
    pass_s = []
    while True:
        t = time.perf_counter()
        before = _state(server)
        families = defaultdict(list)
        for chunk in chunks:
            groups = server._schedule(chunk, stats, account=False)
            for key, items in groups.items():
                if len(families[key]) < mb * n_parts:
                    families[key].extend(
                        (len(chunk), it) for it in items)
        pass_s.append(time.perf_counter() - t)
        # without a pool the schedule holds no state: one pass reaches
        # every ceiling; with one, residency can regroup the next pass
        if (server.pool is None or _state(server) == before
                or len(pass_s) == 4):
            break
    converged = server.pool is None or _state(server) == before
    t1 = time.perf_counter()
    covered = []
    if server.pool is not None:
        covered = cover(index, log)
        for i in range(0, len(covered), mb):
            chunk = covered[i:i + mb]
            groups = server._schedule(chunk, stats, account=False)
            batch_lib.collect_batch(server._launch(groups, len(chunk),
                                                   stats))
    t2 = time.perf_counter()
    launches = 0
    for key, pairs in families.items():
        n_queries = max(n for n, _ in pairs)
        for size in _ladder(mb * n_parts):
            rows = [it for _, it in (pairs * (size // len(pairs) + 1))[:size]]
            d0 = stats.get("n_dispatches", 0)
            batch_lib.collect_batch(server._launch({key: rows}, n_queries,
                                                   stats))
            launches += 1
            if stats.get("n_dispatches", 0) - d0 > 1:
                break
    t3 = time.perf_counter()
    report = {"distinct_queries": len(distinct),
              "schedule_pass_s": [round(x, 2) for x in pass_s],
              "converged": converged, "families": len(families),
              "cover_queries": len(covered), "cover_s": round(t2 - t1, 2),
              "ladder_launches": launches, "ladder_s": round(t3 - t2, 2),
              "signatures": len(stats.get("signatures", ()))}
    if server.pool is not None:
        report["pool"] = {k: v for k, v in server.pool.stats().items()
                          if k in ("resident_lists", "device_ints",
                                   "arena_rows", "evicted_lists")}
    say(f"warm-up: {report}")
    return report


class Spans:
    """Host spans around the server's calls into each layer, recorded on
    the harness clock and as profiler annotations (``bench.<layer>``)."""

    def __init__(self):
        self.spans: dict[str, list] = {k: [] for k in SPAN_LAYERS}
        self._undo: list = []

    def _wrap(self, layer: str, fn):
        spans = self.spans[layer]
        name = "bench." + layer

        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
            spans.append((t0, time.perf_counter()))
            return out
        return wrapped

    def attach(self, server):
        from repro.index import batch as batch_lib
        for layer, attr in (("schedule", "_schedule"),
                            ("launch", "_launch")):
            if hasattr(server, attr):
                setattr(server, attr,
                        self._wrap(layer, getattr(server, attr)))
                self._undo.append((server, attr, None))
        orig = batch_lib.collect_batch
        batch_lib.collect_batch = self._wrap("collect", orig)
        self._undo.append((batch_lib, "collect_batch", orig))

    def detach(self):
        for obj, attr, orig in reversed(self._undo):
            if orig is None:
                delattr(obj, attr)
            else:
                setattr(obj, attr, orig)
        self._undo.clear()
