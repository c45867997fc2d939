"""The in-program span recorder (``repro.trace``) and the served path's
counters: off, it records nothing and changes no answer; on, every flush
is one tree of spans that requests join by id; ``probe_slots`` /
``probe_slots_useful`` and ``d2h_bytes`` count what the programs really
run over and copy back."""

import asyncio
import sys
import time

import jax
import numpy as np
import pytest

from repro import trace
from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine, source
from repro.index import shard as shard_lib
from repro.launch import server as server_lib

pytestmark = pytest.mark.server

# which span names a span's parent may carry (None: a root)
PARENT = {"flush": None, "schedule": "flush", "launch": "flush",
          "collect": "flush", "resolve": "schedule", "fuse": "schedule",
          "assemble": "launch", "dispatch": "launch", "wait": "collect",
          "copy": "collect", "extract": "collect"}


@pytest.fixture(scope="module")
def uniform():
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=10, seed=33)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


def _assert_identical(results, seq):
    assert len(results) == len(seq)
    for got, want in zip(results, seq):
        assert got.count == want.count
        assert got.docs.dtype == want.docs.dtype
        assert np.array_equal(got.docs, want.docs)


@pytest.fixture
def annotations(monkeypatch):
    """Count the profiler annotations the recorder opens."""
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name):
        made.append(name)
        return real(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    return made


@pytest.fixture
def fast_switch():
    """Switch threads every microsecond, so the collector thread and the
    event loop interleave as finely as they can."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        yield
    finally:
        sys.setswitchinterval(old)


@pytest.mark.parametrize("recording", [False, True])
@pytest.mark.parametrize("pool", [False, True])
def test_server_spans(uniform, annotations, fast_switch, recording, pool):
    idx, queries, seq = uniform
    kw = {}
    if pool:
        kw["pool"] = source.ResidentPool()
        kw["pool"].warm(idx)
    srv = server_lib.ContinuousBatchingServer(idx, max_batch=3, depth=2,
                                              drain=True, **kw)
    if recording:
        trace.start()
    try:
        results = asyncio.run(srv.run(queries))
    finally:
        spans = trace.stop()
    _assert_identical(results, seq)
    if not recording:
        # the code path of the recorder-off server: no span, no annotation,
        # no flush id
        assert spans == [] and annotations == []
        assert all(r.flush == -1 for r in srv.requests)
        return
    assert len(annotations) == len(spans)
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans)
    flushes = {s.id for s in spans if s.name == "flush"}
    assert len(flushes) == srv.metrics.n_flushes
    own = trace.self_ns(spans)
    for s in spans:
        assert s.t1_ns >= s.t0_ns and own[s.id] >= 0
        if PARENT[s.name] is None:
            assert s.parent == -1 and s.flush == s.id
        else:
            assert by_id[s.parent].name == PARENT[s.name]
            assert s.flush == by_id[s.parent].flush
            assert s.flush in flushes
    for f in flushes:
        names = {s.name for s in spans if s.flush == f}
        assert {"schedule", "resolve", "fuse", "launch", "assemble",
                "dispatch", "collect", "wait", "copy", "extract"} <= names
    assert {r.flush for r in srv.requests} == flushes
    # a request joins its flush's stages through Request.flush
    rows = trace.by_flush(spans)
    assert set(rows) == flushes
    for r in srv.requests:
        assert {"flush", "schedule", "launch", "collect"} <= set(rows[r.flush])


def test_self_ns_clips_and_merges_children():
    S = trace.Span
    spans = [S(0, "launch", 100, 200, -1, -1),
             S(1, "assemble", 90, 130, 0, -1),      # clipped at 100
             S(2, "dispatch", 120, 150, 0, -1),     # overlaps the first
             S(3, "assemble", 190, 260, 0, -1)]     # clipped at 200
    own = trace.self_ns(spans)
    # launch covers [100, 150) U [190, 200) = 60 of its 100 ns
    assert own == {0: 40, 1: 40, 2: 30, 3: 70}
    assert trace.totals_ns(spans) == {"launch": 100, "assemble": 110,
                                      "dispatch": 30}
    assert trace.self_totals_ns(spans) == {"launch": 40, "assemble": 110,
                                           "dispatch": 30}


def test_by_flush_sums_each_flush():
    S = trace.Span
    spans = [S(0, "flush", 0, 100, -1, 0),
             S(1, "schedule", 0, 40, 0, 0),
             S(2, "resolve", 0, 10, 1, 0),
             S(3, "resolve", 10, 30, 1, 0),
             S(4, "flush", 50, 80, -1, 4),
             S(5, "collect", 60, 80, 4, 4),
             S(6, "resolve", 0, 5, -1, -1)]         # outside any flush
    assert trace.by_flush(spans) == {
        0: {"flush": 100, "schedule": 40, "resolve": 30},
        4: {"flush": 30, "collect": 20}}


def test_flush_closed_after_stop_is_dropped():
    trace.start()
    fl = trace.flush()
    with trace.span("schedule", parent=fl):
        pass
    spans = trace.stop()
    trace.end(fl)
    assert [s.name for s in spans] == ["schedule"]
    assert trace.flush() is None and trace.span("x") is trace._OFF


@pytest.fixture(scope="module")
def probe_index():
    """Two parts of 2048 docs.  Term 0 holds every other doc: a bitmap in
    both parts (average gap 2 ≤ B).  Terms 1-3 are short lists: 10 + 7,
    5 + 12 and 30 + 40 docs in parts 0 + 1."""
    rng = np.random.default_rng(4)

    def pick(n0, n1):
        return np.concatenate([np.sort(rng.choice(2048, n0, replace=False)),
                               2048 + np.sort(rng.choice(2048, n1,
                                                         replace=False))])

    postings = [np.arange(0, 4096, 2), pick(10, 7), pick(5, 12), pick(30, 40)]
    idx = builder.build([p.astype(np.int32) for p in postings], 4096,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    assert [idx.parts[p].terms[t].kind for p in (0, 1) for t in range(4)] \
        == ["bitmap", "list", "list", "list"] * 2
    # seed 1 probing 0; seed 2 probing 0; seed 1 folding 3, no probe
    return idx, [[1, 0], [2, 0], [1, 3]]


@pytest.mark.parametrize("mode,slots", [
    # the probe gathers chunks of C = 4 seed slots up to the longest real
    # seed among rows with a bitmap: ceil(12 / 4) = 3 chunks of bitmap
    # slot 0 (M = 128 would gather 32).  The two probing queries' four rows
    # share one key: 3 chunks × C 4 × Bp 4
    ("unfused", 3 * 4 * 4),
    # one family of all six rows, the fold-only query's two padding the
    # probe: 3 × 4 × Bp 6
    ("fused", 3 * 4 * 6),
    # two shards of three rows each, one extent over both: 3 × 4 × (S 2 ×
    # Bq 3)
    ("sharded", 3 * 4 * 6),
])
def test_probe_slot_counters(probe_index, monkeypatch, mode, slots):
    idx, queries = probe_index
    monkeypatch.setattr(batch_lib, "PROBE_CHUNK", 4)
    stats = {}
    if mode == "sharded":
        out = shard_lib.execute_sharded(shard_lib.shard_index(idx, 2),
                                        queries, batch_size=3, stats=stats)
    else:
        out = batch_lib.execute_batch(idx, queries, fuse=mode == "fused",
                                      stats=stats)
    _assert_identical(out, [engine.query(idx, q) for q in queries])
    assert stats["probe_slots"] == slots
    # real seed length × real bitmap count, per real row:
    # (10 + 7) × 1 + (5 + 12) × 1 + (10 + 7) × 0
    assert stats["probe_slots_useful"] == 34


@pytest.mark.parametrize("mode,ceiling", [
    # the fold-only query's seed (term 1: 10 + 7 docs) folds term 3 in
    # each part: two active slots of one tile each; unfused, M = 128 is
    # the seed's own bucket, one tile a slot
    ("unfused", 2 * 1),
    # the plan pins the family's M at 512: a full walk is 4 tiles a slot,
    # the real one still 1
    ("fused", 2 * 4),
    # two shards of three rows, the same family ceiling
    ("sharded", 2 * 4),
])
def test_fold_tile_counters(probe_index, monkeypatch, mode, ceiling):
    """``fold_tiles`` counts the tile steps the fold megakernels walk, each
    active (j, b) slot its row's ``seed_tiles``; ``fold_tiles_ceiling``
    the same slots at M / 128 tiles, a walk of the whole padded row.
    Rows without a fold (the probing queries) and padded rows add
    nothing."""
    idx, queries = probe_index
    monkeypatch.setattr(batch_lib, "PALLAS_MIN_OCCUPANCY", 0.0)
    plan = batch_lib.FusionPlan()
    plan.raised(("svs", None), (512, 0, 0, 0, 0))
    stats = {}
    if mode == "sharded":
        out = shard_lib.execute_sharded(shard_lib.shard_index(idx, 2),
                                        queries, batch_size=3,
                                        backend="pallas", plan=plan,
                                        stats=stats)
    else:
        out = batch_lib.execute_batch(idx, queries, backend="pallas",
                                      fuse=mode == "fused", plan=plan,
                                      stats=stats)
    _assert_identical(out, [engine.query(idx, q) for q in queries])
    assert stats["kernel_folds"] == 2
    assert stats["fold_tiles"] == 2
    assert stats["fold_tiles_ceiling"] == ceiling


@pytest.mark.parametrize("depth", [1, 2])
def test_d2h_bytes(uniform, monkeypatch, fast_switch, depth):
    """``d2h_bytes`` is the bytes of every collected chunk's vals and
    counts, added on the event loop, also while a slow collector thread
    holds ``depth`` flushes in flight."""
    idx, queries, seq = uniform
    seen = []
    real = batch_lib.collect_batch

    def slow_collect(pending):
        time.sleep(0.01)
        seen.append(sum(v.nbytes + c.nbytes
                        for _, _, v, c in pending.launched))
        out = real(pending)
        assert pending.d2h_bytes == seen[-1]
        return out

    monkeypatch.setattr(batch_lib, "collect_batch", slow_collect)
    results, srv = server_lib.serve_open_loop(idx, queries, qps=0.0,
                                              max_batch=2, depth=depth)
    _assert_identical(results, seq)
    assert len(seen) == srv.metrics.n_flushes
    assert srv.stats["d2h_bytes"] == sum(seen) > 0
