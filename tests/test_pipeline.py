"""Differential coverage for the device-resident index and the pipelined
executor (ISSUE 3).

Layers:
  * pool-backed batch execution == sequential engine (byte-identical), with
    resident-hit accounting actually firing,
  * pipelined execution at depth ∈ {1, 2, 4} == ``engine.query`` across
    jax/pallas backends and uniform/skewed corpora, plus the empty-batch and
    single-query edges,
  * ResidentPool staging/eviction accounting, layout-memo counters, and the
    build-time layout precompute.
"""

import numpy as np
import pytest

from repro import trace
from repro.index import batch as batch_lib
from repro.index import pipeline as pipe_lib
from repro.index import builder, corpus as corpus_lib, engine, source
from repro.launch import serve as serve_lib

pytestmark = pytest.mark.pipeline


# --------------------------------------------------------------------------
# fixtures: uniform (Table-2-shaped) and skewed-ratio corpora
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def uniform():
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=10, seed=33)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


@pytest.fixture(scope="module")
def skewed():
    # tiny first term, very long second term: exercises the packed
    # (skip-aware partial decode) folds through the pipeline
    n_docs = 1 << 16
    table = {2: (100.0, [0.8 * (1 << 18) / n_docs,
                         38000.0 * (1 << 18) / n_docs])}
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=4, seed=7,
                                   table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="bp8-d1", B=0, n_parts=1)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


def _assert_identical(results, seq):
    assert len(results) == len(seq)
    for got, want in zip(results, seq):
        assert got.count == want.count
        assert got.docs.dtype == want.docs.dtype
        assert np.array_equal(got.docs, want.docs)      # byte-identical


# --------------------------------------------------------------------------
# pool-backed batch execution
# --------------------------------------------------------------------------

def test_pool_batch_matches_sequential(uniform):
    idx, queries, seq = uniform
    pool = source.ResidentPool()
    pool.warm(idx)
    stats: dict = {}
    _assert_identical(
        batch_lib.execute_batch(idx, queries, pool=pool, stats=stats), seq)
    assert stats.get("resident_hits", 0) > 0
    # steady state: a second pass decodes nothing at all
    stats2: dict = {}
    _assert_identical(
        batch_lib.execute_batch(idx, queries, pool=pool, stats=stats2), seq)
    assert stats2.get("decoded_lists", 0) == 0


def test_pool_composes_with_cache(uniform):
    idx, queries, seq = uniform
    pool = source.ResidentPool()
    cache = engine.DecodeCache(capacity_ints=1 << 24)
    for _ in range(2):
        _assert_identical(
            batch_lib.execute_batch(idx, queries, pool=pool, cache=cache),
            seq)


def test_pool_lazy_staging_converges(uniform):
    """Without warm(), the first batch decodes and stages; the second batch
    serves from residency."""
    idx, queries, _ = uniform
    pool = source.ResidentPool()
    batch_lib.execute_batch(idx, queries, pool=pool)
    staged = pool.staged_lists
    assert staged > 0
    stats: dict = {}
    batch_lib.execute_batch(idx, queries, pool=pool, stats=stats)
    assert pool.staged_lists == staged          # nothing new staged
    assert stats.get("decoded_lists", 0) == 0


# --------------------------------------------------------------------------
# pipelined execution: depth × backend × corpus differential matrix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_pipeline_matches_sequential_uniform(uniform, depth, backend):
    idx, queries, seq = uniform
    pool = source.ResidentPool()
    pool.warm(idx)
    out = pipe_lib.execute_pipelined(idx, queries, batch_size=4, depth=depth,
                                     backend=backend, pool=pool)
    _assert_identical(out, seq)


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_pipeline_matches_sequential_skewed(skewed, depth, backend):
    idx, queries, seq = skewed
    out = pipe_lib.execute_pipelined(idx, queries, batch_size=2, depth=depth,
                                     backend=backend)
    _assert_identical(out, seq)


def test_pipeline_empty_batch(uniform):
    idx, _, _ = uniform
    assert pipe_lib.execute_pipelined(idx, [], batch_size=8, depth=2) == []


def test_pipeline_single_query(uniform):
    idx, queries, seq = uniform
    for depth in (1, 2, 4):
        out = pipe_lib.execute_pipelined(idx, [queries[0]], batch_size=8,
                                         depth=depth)
        _assert_identical(out, seq[:1])


def test_pipeline_depth_one_equals_execute_batch(uniform):
    idx, queries, _ = uniform
    pool = source.ResidentPool()
    pool.warm(idx)
    serial = []
    for lo in range(0, len(queries), 4):
        serial.extend(batch_lib.execute_batch(idx, queries[lo: lo + 4],
                                              pool=pool))
    piped = pipe_lib.execute_pipelined(idx, queries, batch_size=4, depth=1,
                                       pool=pool)
    _assert_identical(piped, serial)


def test_pipeline_timings_populated(uniform):
    """The recorder's totals of a pipelined run: one flush per batch, each
    with its schedule, launch and collect, the launcher's assemble and
    dispatch spans under launch, all four stage totals above 0."""
    idx, queries, seq = uniform
    trace.start()
    try:
        out = pipe_lib.execute_pipelined(idx, queries, batch_size=4,
                                         depth=2)
    finally:
        spans = trace.stop()
    _assert_identical(out, seq)
    n_batches = (len(queries) + 3) // 4
    by_id = {s.id: s for s in spans}
    flushes = [s for s in spans if s.name == "flush"]
    assert len(flushes) == n_batches
    for name in ("schedule", "launch", "collect"):
        stage = [s for s in spans if s.name == name]
        assert sorted(by_id[s.parent].id for s in stage) == \
            sorted(f.id for f in flushes)
    for s in spans:
        if s.name in ("assemble", "dispatch"):
            assert by_id[s.parent].name == "launch"
    tot = trace.totals_ns(spans)
    assert all(tot[k] > 0 for k in ("schedule", "assemble", "dispatch",
                                    "collect"))
    line = serve_lib.stage_line(spans)
    assert "assemble" in line and "of which fuse" in line


# --------------------------------------------------------------------------
# pool accounting + layout memoization
# --------------------------------------------------------------------------

def test_pool_eviction_accounting(uniform):
    idx, queries, seq = uniform
    pool = source.ResidentPool(capacity_ints=2048)      # tiny: forces churn
    _assert_identical(batch_lib.execute_batch(idx, queries, pool=pool), seq)
    st = pool.stats()
    assert st["evicted_lists"] > 0
    # budget respected (a single oversized entry is the only exception)
    assert st["resident_lists"] == 1 or st["resident_ints"] <= 2048
    assert st["staged_ints"] - st["evicted_ints"] == st["resident_ints"]


def test_pool_churn_bounds_device_footprint(uniform):
    """ISSUE 6 regression: under eviction churn, the *full* device
    footprint (store entries + pad memos + arena row copies) stops
    growing — previously every arena kept a device copy of every row ever
    staged and pad memos outlived their entries, so real device memory
    grew without bound while ``resident_ints`` claimed the budget held."""
    idx, queries, seq = uniform
    pool = source.ResidentPool(capacity_ints=2048)      # tiny: forces churn
    for _ in range(2):                                   # reach steady churn
        _assert_identical(batch_lib.execute_batch(idx, queries, pool=pool),
                          seq)
    st1 = pool.stats()
    assert st1["evicted_lists"] > 0
    for _ in range(3):                                   # keep churning
        _assert_identical(batch_lib.execute_batch(idx, queries, pool=pool),
                          seq)
    st2 = pool.stats()
    assert st2["evicted_lists"] > st1["evicted_lists"]   # churn continued...
    # ...but the allocated arena footprint stopped growing (slot reuse)
    assert st2["arena_ints"] == st1["arena_ints"]
    assert st2["overhead_ints"] == st1["overhead_ints"]
    assert st2["arena_evictions"] > 0
    # pad accounting has no drift: the aggregate counter equals the sum
    # over live entries (evicted entries dropped their memos)
    assert st2["pad_ints"] == sum(e["pad_ints"]
                                  for e in pool._store.values())
    assert all(not e["pads"] or e["pad_ints"] > 0
               for e in pool._store.values())
    # the store invariant survives the new accounting
    assert st2["staged_ints"] - st2["evicted_ints"] == st2["resident_ints"]
    assert st2["device_ints"] == st2["resident_ints"] + st2["overhead_ints"]


def test_arena_evict_reuses_slots():
    """RowArena.evict frees the slot for the next miss — allocated
    footprint (and therefore the gather buffer shape) stays flat under
    churn."""
    a = source.RowArena([np.zeros(4, np.int32)])
    s1 = a.slot("a", lambda: np.ones(4, np.int32))
    a.slot("b", lambda: np.full(4, 2, np.int32))
    ints0 = a.ints
    assert a.evict("a") == 4
    assert a.evict("missing") == 0
    s3 = a.slot("c", lambda: np.full(4, 3, np.int32))
    assert s3 == s1                         # freed slot reused
    assert a.ints == ints0                  # no growth
    assert a.evictions == 1
    buf = np.asarray(a.buffer())
    assert np.array_equal(buf[s3], np.full(4, 3, np.int32))


def test_pool_warm_skips_long_skip_capable_lists(skewed):
    """warm() keeps skip-served lists compressed — residency must not
    silently decompress the index."""
    idx, queries, seq = skewed
    pool = source.ResidentPool()
    pool.warm(idx)
    stats: dict = {}
    _assert_identical(
        batch_lib.execute_batch(idx, queries, pool=pool, stats=stats), seq)
    assert stats.get("skip_folds", 0) > 0       # packed path still taken


def test_demoted_geometry_mismatch_stays_out_of_pool():
    """A packed fold demoted over a block-geometry mismatch is decoded for
    that group only: staging it resident would evict hot short lists and
    permanently win over want_skip, disabling its skip path."""
    rng = np.random.default_rng(0)
    n_docs = 1 << 18
    postings = [
        np.sort(rng.choice(n_docs, 50, replace=False)),      # seed
        np.sort(rng.choice(n_docs, 6000, replace=False)),    # 8-row blocks
        np.sort(rng.choice(n_docs, 40000, replace=False)),   # 32-row blocks
    ]
    idx = builder.build(postings, n_docs, codec_name="bp-d1", B=0, n_parts=1)
    q = [0, 1, 2]
    seq = engine.query(idx, q)
    pool = source.ResidentPool()
    pool.warm(idx)
    for _ in range(2):
        stats: dict = {}
        out = batch_lib.execute_batch(idx, [q], pool=pool, stats=stats)
        assert out[0].count == seq.count
        assert np.array_equal(out[0].docs, seq.docs)
        # the 40k list is skip-served every pass; the demoted 6k list is
        # decoded per group, never staged
        assert stats.get("skip_folds", 0) == 1
        assert stats.get("decoded_lists", 0) == 1
        assert (idx.parts[0].uid, 1) not in pool


def test_layout_precomputed_at_build(skewed):
    """builder.build warms the self-padded layout memo: the sequential
    packed probe never projects on the query path."""
    idx, queries, _ = skewed
    stats: dict = {}
    engine.query(idx, queries[0], stats=stats)
    assert stats.get("layout_misses", 0) == 0
    assert stats.get("layout_hits", 0) > 0


def test_decoded_source_vals_np_consistent(uniform):
    idx, _, _ = uniform
    from repro.core import codecs as codec_lib
    codec = codec_lib.get_codec(idx.codec_name)
    part = idx.parts[0]
    tid, tp = next((t, tp) for t, tp in part.terms.items()
                   if tp.kind == "list")
    src = source.resolve(part, tid, tp, codec, r_count=None)
    assert src.vals_np is not None
    assert np.array_equal(np.asarray(src.vals), src.vals_np)
