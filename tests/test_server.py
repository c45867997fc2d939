"""Continuous-batching server coverage (ISSUE 6).

Layers:
  * differential guarantee: served results == offline ``execute_batch`` on
    the same query multiset across {jax, pallas} × {fused, unfused} ×
    shards {1, 2}, in drain and live (open-loop) modes, under any arrival
    order,
  * steady state: a warmed server reports n_compiles == 0,
  * the three loop policies: family-aligned admission after warmup,
    deadline flush vs full flush, bounded-queue shedding,
  * unit coverage for the arrival processes, ``batch.plan_covers`` and the
    ``warm_to_fixed_point`` convergence flag.
"""

import asyncio

import numpy as np
import pytest

from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine, segments, \
    source
from repro.index import shard as shard_lib
from repro.launch import server as server_lib

pytestmark = pytest.mark.server


# --------------------------------------------------------------------------
# fixtures (mirrors tests/test_fusion.py)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def uniform():
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=10, seed=33)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


@pytest.fixture(scope="module")
def mixed():
    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=32, seed=11,
                                   table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


def _assert_identical(results, seq):
    assert len(results) == len(seq)
    for got, want in zip(results, seq):
        assert got.count == want.count
        assert got.docs.dtype == want.docs.dtype
        assert np.array_equal(got.docs, want.docs)      # byte-identical



# --------------------------------------------------------------------------
# differential: served == offline, {backend} × {fuse} × {drain, live}
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("fuse", [True, False])
def test_server_matches_offline(uniform, backend, fuse):
    idx, queries, seq = uniform
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=0.0, backend=backend, fuse=fuse, max_batch=4)
    assert srv.metrics.n_shed == 0
    _assert_identical(results, seq)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_server_live_load_matches_offline(uniform, backend):
    idx, queries, seq = uniform
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=2000.0, pattern="poisson", seed=3,
        backend=backend, max_batch=4, max_queue=1024, max_wait_ms=1.0)
    assert srv.metrics.n_shed == 0
    _assert_identical(results, seq)
    s = srv.metrics.summary()
    assert s["n_done"] == len(queries)
    assert s["p99_ms"] >= s["p50_ms"] > 0
    assert sum(s["queue_depth_hist"].values()) == len(queries)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_server_sharded_matches_offline(uniform, n_shards):
    idx, queries, seq = uniform
    sharded = shard_lib.shard_index(idx, n_shards)
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=0.0, sharded=sharded, max_batch=4)
    _assert_identical(results, seq)


def test_server_arrival_order_independent(mixed):
    """Any packing of the same query multiset returns per-query results
    identical to the sequential engine."""
    idx, queries, seq = mixed
    perm = np.random.default_rng(9).permutation(len(queries))
    shuffled = [queries[i] for i in perm]
    results, _ = server_lib.serve_open_loop(idx, shuffled, qps=0.0,
                                            max_batch=8)
    for out_i, src_i in enumerate(perm):
        _assert_identical([results[out_i]], [seq[src_i]])


def test_server_pool_composes(uniform):
    idx, queries, seq = uniform
    pool = source.ResidentPool()
    pool.warm(idx)
    results, srv = server_lib.serve_open_loop(idx, queries, qps=0.0,
                                              pool=pool, max_batch=4)
    _assert_identical(results, seq)
    assert srv.stats.get("resident_hits", 0) > 0


# --------------------------------------------------------------------------
# steady state: warmed server compiles nothing
# --------------------------------------------------------------------------

def test_server_steady_state_zero_compiles(mixed):
    idx, queries, seq = mixed
    pool = source.ResidentPool()
    pool.warm(idx)
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=0.0, warmup=True, pool=pool, max_batch=8)
    wu = srv.warm_report
    assert wu["converged"] and wu["n_signatures"] > 0
    # drain mode after warmup: deterministic full batches, zero compiles
    assert srv.stats.get("n_compiles", 0) == 0
    _assert_identical(results, seq)
    # every flush was family-aligned (the sticky plan covered its groups
    # before fusion — the property that makes steady state compile-free)
    m = srv.metrics
    assert m.unaligned_flushes == 0
    assert m.aligned_flushes == m.n_flushes > 0


# --------------------------------------------------------------------------
# loop policies: flush reasons + backpressure
# --------------------------------------------------------------------------

def test_server_drain_mode_flushes_full_batches(mixed):
    idx, queries, seq = mixed                   # 32 queries
    results, srv = server_lib.serve_open_loop(idx, queries, qps=0.0,
                                              max_batch=8)
    m = srv.metrics
    assert m.flush_deadline == 0                # drain mode never deadlines
    assert m.flush_full + m.flush_drain == m.n_flushes == 4
    _assert_identical(results, seq)


def test_server_deadline_flush_fires(uniform):
    """Arrivals far slower than max_wait: every batch launches on the
    deadline (or the end-of-stream drain), never on max_batch."""
    idx, queries, seq = uniform
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=200.0, pattern="uniform", max_batch=32,
        max_wait_ms=0.5, max_queue=64)
    m = srv.metrics
    assert m.flush_full == 0
    assert m.flush_deadline >= 1
    assert srv.metrics.n_shed == 0
    _assert_identical(results, seq)


def test_server_bounded_queue_sheds(uniform):
    """Open-loop arrivals that find the queue full are shed and counted —
    submitting with no await between arrivals means the batcher never
    runs, so exactly max_queue requests are admitted."""
    idx, queries, seq = uniform
    many = queries * 4
    srv = server_lib.ContinuousBatchingServer(idx, max_batch=4, max_queue=4)
    results = asyncio.run(srv.run(many, [0.0] * len(many)))
    assert srv.metrics.n_shed == len(many) - 4
    served = [r for r in results if r is not None]
    assert len(served) == 4
    _assert_identical(served, seq[:4])          # first 4 arrivals admitted
    s = srv.metrics.summary()
    assert s["n_shed"] == len(many) - 4


# --------------------------------------------------------------------------
# unit: arrival processes, plan_covers, convergence flag
# --------------------------------------------------------------------------

def test_arrival_gaps_shapes():
    assert server_lib.arrival_gaps(5, 0.0) == [0.0] * 5
    assert server_lib.arrival_gaps(0, 100.0) == []
    u = server_lib.arrival_gaps(4, 100.0, "uniform")
    assert u == [0.01] * 4
    g = server_lib.arrival_gaps(2000, 100.0, "poisson", seed=1)
    assert all(x >= 0 for x in g)
    assert 0.005 < float(np.mean(g)) < 0.02     # mean ≈ 1/qps
    b = server_lib.arrival_gaps(16, 100.0, "bursty", seed=1, burst=8)
    assert all(x == 0.0 for x in b[1:8])        # within-burst: no gap
    assert all(x == 0.0 for x in b[9:16])
    with pytest.raises(ValueError):
        server_lib.arrival_gaps(4, 100.0, "sawtooth")


def test_plan_covers_predicate(mixed):
    """The admission predicate: an empty plan covers nothing; after one
    fused batch the sticky ceilings cover any narrower batch — checked
    *before* fuse_groups raises ceilings."""
    idx, queries, _ = mixed
    plan = batch_lib.FusionPlan()
    groups = batch_lib.schedule(idx, queries)
    assert not batch_lib.plan_covers(groups, plan)
    assert not batch_lib.plan_covers(groups, None)
    batch_lib.fuse_groups(dict(groups), plan=plan)
    sub = batch_lib.schedule(idx, queries[:3])
    assert batch_lib.plan_covers(sub, plan)
    assert batch_lib.plan_covers({}, plan)      # empty flush: nothing new


def test_warm_to_fixed_point_reports_convergence():
    calls = []

    def never_settles(stats):
        calls.append(1)
        stats.setdefault("signatures", set()).add(len(calls))

    n, passes, converged = batch_lib.warm_to_fixed_point(never_settles,
                                                         max_passes=3)
    assert passes == 3 and not converged and n == 3

    def settles(stats):
        stats.setdefault("signatures", set()).add(1)

    n, passes, converged = batch_lib.warm_to_fixed_point(settles)
    assert converged and n == 1 and passes == 2


# --------------------------------------------------------------------------
# live mutation (ISSUE 9): a MutableIndex behind the server
# --------------------------------------------------------------------------

def _mutable_setup(n_queries=16, seed=7):
    corpus = corpus_lib.synthesize(n_docs=1 << 13, n_queries=n_queries,
                                   seed=seed)
    mi = segments.MutableIndex.from_postings(
        corpus.postings, corpus.n_docs, codec_name="fastpfor-d1", B=16,
        n_parts=2)
    terms = sorted({t for q in corpus.queries for t in q})
    return mi, corpus, terms


def test_server_live_mutation_windows_match_offline():
    """Rounds of adds/deletes between Poisson serving windows: every
    window's served results equal offline ``MutableIndex.execute_batch``
    on the then-current state, at zero compiles once warmed — including
    across a seal + background-style merge (generation swap)."""
    mi, corpus, terms = _mutable_setup()
    stats: dict = {}
    srv = server_lib.ContinuousBatchingServer(
        mutable=mi, max_batch=4, max_wait_ms=1.0, max_queue=1024,
        stats=stats)
    wu = server_lib.warm_server(srv, corpus.queries)
    assert wu["converged"]
    rng = np.random.default_rng(2)

    def mutate(n_adds=20, n_dels=5):
        for _ in range(n_adds):
            k = int(rng.integers(1, min(4, len(terms)) + 1))
            doc = sorted(rng.choice(terms, size=k, replace=False).tolist())
            mi.add(doc)
        for _ in range(n_dels):
            mi.delete(int(rng.integers(0, mi.next_doc_id)))

    def window(seed, steady=True):
        stats.pop("n_compiles", None)
        gaps = server_lib.arrival_gaps(len(corpus.queries), 2000.0,
                                       "poisson", seed=seed)
        results = asyncio.run(srv.run(corpus.queries, gaps))
        assert srv.metrics.n_shed == 0
        offline = mi.execute_batch(corpus.queries)
        _assert_identical(results, offline)
        if steady:
            assert stats.get("n_compiles", 0) == 0

    # window 0 converges the plan: the AOT ladder warms contiguous chunk
    # packings, live Poisson packings are arbitrary subsets — the first
    # window raises the family ceilings over them, after which the sticky
    # plan covers ANY packing (the steady-state claim under test)
    mutate()
    window(seed=0, steady=False)
    for r in range(1, 3):
        mutate()
        window(seed=r)

    # generation swap: seal + merge pre-warmed through the *shared* sticky
    # plan; the first post-swap window must still compile nothing
    mutate()
    assert mi.seal() is not None
    assert mi.merge(warm_queries=corpus.queries) is True
    window(seed=99)
    assert mi.counters()["n_merges"] == 1


def test_server_mutations_between_flushes_under_poisson():
    """Mutations injected *between flushes* (at the server's snapshot
    seam) while Poisson traffic is in flight: each flush's served results
    must equal a python set-model oracle evaluated at that flush's
    snapshot — the per-flush byte-identity the windowed test can't see."""
    mi, corpus, terms = _mutable_setup()
    model = {t: set(corpus.postings[t].tolist()) for t in terms}
    dead: set[int] = set()
    rng = np.random.default_rng(4)

    # depth=1 serializes flush -> collect -> next flush, so the model is
    # stable from each snapshot through its finalize
    srv = server_lib.ContinuousBatchingServer(
        mutable=mi, max_batch=4, max_wait_ms=1.0, max_queue=1024, depth=1)
    server_lib.warm_server(srv, corpus.queries)

    muts = iter(range(64))

    def mutate_once():
        if next(muts, None) is None:
            return
        for _ in range(3):
            k = int(rng.integers(1, min(4, len(terms)) + 1))
            doc = sorted(rng.choice(terms, size=k, replace=False).tolist())
            gid = mi.add(doc)
            for t in doc:
                model[t].add(gid)
        d = int(rng.integers(0, mi.next_doc_id))
        mi.delete(d)
        dead.add(d)

    orig_snapshot = srv._snapshot

    def snapshot_with_mutation():
        mutate_once()
        return orig_snapshot()

    srv._snapshot = snapshot_with_mutation

    def oracle(q):
        alive = set.intersection(*[model[t] for t in q]) - dead
        return np.asarray(sorted(alive), dtype=np.int64)

    checked = []
    orig_finalize = mi.finalize

    def checking_finalize(snap, queries, results, max_results=1 << 16):
        out = orig_finalize(snap, queries, results, max_results)
        for q, r in zip(queries, out):
            want = oracle(q)
            assert r.count == want.size, (q, r.count, want.size)
            assert np.array_equal(r.docs, want)
            checked.append(1)
        return out

    mi.finalize = checking_finalize
    try:
        stream = corpus.queries * 3
        gaps = server_lib.arrival_gaps(len(stream), 1500.0, "poisson",
                                       seed=5)
        results = asyncio.run(srv.run(stream, gaps))
    finally:
        mi.finalize = orig_finalize
        srv._snapshot = orig_snapshot
    assert srv.metrics.n_shed == 0
    assert all(r is not None for r in results)
    assert len(checked) == len(stream)          # every request was checked
    assert mi.counters()["mutable_docs"] > 0    # mutations really landed
    assert mi.counters()["tombstones"] > 0


@pytest.mark.parametrize("n_shards", [1, 2])
def test_server_mutable_sharded_matches_offline(n_shards):
    mi, corpus, terms = _mutable_setup(n_queries=10, seed=21)
    if n_shards > 1:
        mi = segments.MutableIndex.from_postings(
            corpus.postings, corpus.n_docs, codec_name="fastpfor-d1",
            B=16, n_parts=2, n_shards=n_shards)
    rng = np.random.default_rng(8)
    for _ in range(15):
        k = int(rng.integers(1, min(4, len(terms)) + 1))
        mi.add(sorted(rng.choice(terms, size=k, replace=False).tolist()))
    for _ in range(4):
        mi.delete(int(rng.integers(0, mi.next_doc_id)))
    results, srv = server_lib.serve_open_loop(
        None, corpus.queries, qps=0.0, mutable=mi, max_batch=4)
    offline = mi.execute_batch(corpus.queries)
    _assert_identical(results, offline)


# --------------------------------------------------------------------------
# resolution audit (DESIGN.md §2.15): no request ever goes unresolved
# --------------------------------------------------------------------------

def test_server_every_request_resolves_with_explicit_outcome(uniform):
    """Shed, timed-out and served requests all finish with their ``done``
    event set and an explicit entry in ``outcomes()`` — no awaiter can
    hang, drain mode included.  Here the queue bound sheds most of the
    stream and a microscopic deadline expires the admitted rest."""
    idx, queries, _ = uniform
    many = queries * 4
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=4, timeout_ms=1e-4)
    results = asyncio.run(srv.run(many, [0.0] * len(many)))
    outs = srv.outcomes()
    assert len(outs) == len(many)
    assert "pending" not in outs
    assert outs.count("shed") == len(many) - 4
    assert outs.count("timeout") == 4           # every admitted one expired
    assert all(r is None for r in results)
    assert all(r is None or r.done.is_set() for r in srv.requests)
    s = srv.metrics.summary()
    assert s["n_timeout"] == 4 and s["n_shed"] == len(many) - 4


def test_server_generous_timeout_serves_everything(uniform):
    """A deadline far above service time must change nothing: all done,
    byte-identical, zero timeout outcomes."""
    idx, queries, seq = uniform
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=0.0, max_batch=4, timeout_ms=60_000.0)
    assert srv.outcomes() == ["done"] * len(queries)
    assert srv.metrics.n_timeout == 0
    _assert_identical(results, seq)
