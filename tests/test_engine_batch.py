"""Differential coverage for the query engine and the batched scheduler.

Three layers (ISSUE 1):
  * DecodeCache size accounting regression (re-putting a key must not drift),
  * randomized corpora (codec × part-count × list/bitmap mixes) asserting
    ``engine.query`` == ``brute_force``,
  * batched-vs-sequential equivalence: ``batch.execute_batch`` must return
    byte-identical counts and doc ids for every query, on both backends.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine


# --------------------------------------------------------------------------
# DecodeCache regression
# --------------------------------------------------------------------------

def test_decode_cache_reput_size_stable():
    cache = engine.DecodeCache(capacity_ints=1 << 20)
    vals = jnp.zeros((256,), jnp.int32)
    for _ in range(5):
        cache.put("k", vals, 200)
    assert cache._size == 256            # was 5×256 before the fix
    bigger = jnp.zeros((512,), jnp.int32)
    cache.put("k", bigger, 400)
    assert cache._size == 512
    assert cache.get("k")[1] == 400


def test_decode_cache_reput_does_not_evict_prematurely():
    cache = engine.DecodeCache(capacity_ints=1024)
    a = jnp.zeros((400,), jnp.int32)
    b = jnp.zeros((400,), jnp.int32)
    cache.put("a", a, 400)
    cache.put("b", b, 400)
    for _ in range(10):                  # drifting _size used to evict here
        cache.put("a", a, 400)
    assert cache.get("b") is not None
    assert cache._size == 800


def test_decode_cache_distinct_across_rebuilds():
    """Cache keys are part-uid based: rebuilding an index must not share
    (or collide with) entries from a previous build."""
    corpus = corpus_lib.synthesize(n_docs=1 << 12, n_queries=3, seed=9)
    cache = engine.DecodeCache(capacity_ints=1 << 24)
    q = corpus.queries[0]
    idx1 = builder.build(corpus.postings, corpus.n_docs,
                         codec_name="bp-d1", B=0, n_parts=1)
    a = engine.query(idx1, q, cache=cache)
    n_entries = len(cache._store)
    assert n_entries > 0
    idx2 = builder.build(corpus.postings, corpus.n_docs,
                         codec_name="bp-d1", B=0, n_parts=1)
    b = engine.query(idx2, q, cache=cache)
    assert len(cache._store) == 2 * n_entries      # no key collisions
    assert a.count == b.count
    assert np.array_equal(a.docs, b.docs)


# --------------------------------------------------------------------------
# randomized differential matrix
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_corpus():
    return corpus_lib.synthesize(n_docs=1 << 14, n_queries=12, seed=21)


@pytest.mark.parametrize("codec,B,n_parts", [
    ("bp-d1", 0, 1),            # pure compressed lists, single part
    ("bp-dv", 8, 2),            # wide-stride deltas + some bitmaps
    ("fastpfor-d1", 16, 2),     # patched codec + bitmap mix
    ("fastpfor-d1", 64, 3),     # bitmap-heavy (all-bitmap queries appear)
    ("varint", 32, 3),          # tail codec everywhere
])
def test_engine_matches_bruteforce(small_corpus, codec, B, n_parts):
    idx = builder.build(small_corpus.postings, small_corpus.n_docs,
                        codec_name=codec, B=B, n_parts=n_parts)
    for q in small_corpus.queries:
        got = engine.query(idx, q)
        expect = engine.brute_force(small_corpus.postings, q)
        assert got.count == len(expect)
        assert np.array_equal(np.sort(got.docs), expect[: len(got.docs)])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_engine_matches_bruteforce_random_seeds(seed):
    corpus = corpus_lib.synthesize(n_docs=1 << 13, n_queries=6, seed=seed)
    rng = np.random.default_rng(seed)
    codec = rng.choice(["bp-d1", "bp-d2", "fastpfor-d1", "varint"])
    B = int(rng.choice([0, 8, 32]))
    n_parts = int(rng.choice([1, 2, 4]))
    idx = builder.build(corpus.postings, corpus.n_docs, codec_name=codec,
                        B=B, n_parts=n_parts)
    for q in corpus.queries:
        got = engine.query(idx, q)
        expect = engine.brute_force(corpus.postings, q)
        assert got.count == len(expect), (codec, B, n_parts)
        assert np.array_equal(np.sort(got.docs), expect[: len(got.docs)])


# --------------------------------------------------------------------------
# batched vs sequential equivalence
# --------------------------------------------------------------------------

@pytest.mark.parametrize("corpus,codec,B,n_parts", [
    pytest.param("small_corpus", "bp-d1", 0, 1, id="bp-d1-0-1"),
    pytest.param("small_corpus", "fastpfor-d1", 16, 2, id="fastpfor-d1-16-2"),
    # includes all-bitmap groups
    pytest.param("small_corpus", "fastpfor-d1", 64, 3, id="fastpfor-d1-64-3"),
    pytest.param("small_corpus", "varint", 32, 3, id="varint-32-3"),
    # seeds of 0, 1, C - 1, C, C + 1 and M slots across the bitmap probe's
    # chunk edges (conftest), with 0 to 4 bitmaps a row
    pytest.param("probe_edges", "fastpfor-d1", 8, 2, id="probe-edges"),
])
def test_batched_matches_sequential(request, corpus, codec, B, n_parts):
    corpus = request.getfixturevalue(corpus)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name=codec, B=B, n_parts=n_parts)
    stats = {}
    batched = batch_lib.execute_batch(idx, corpus.queries, stats=stats)
    assert len(batched) == len(corpus.queries)
    assert stats["n_dispatches"] > 0
    for q, br in zip(corpus.queries, batched):
        sr = engine.query(idx, q)
        assert sr.count == br.count
        assert br.docs.dtype == sr.docs.dtype
        assert np.array_equal(sr.docs, br.docs)      # byte-identical


def test_batched_pallas_backend_matches(small_corpus):
    idx = builder.build(small_corpus.postings, small_corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    queries = small_corpus.queries[:6]
    batched = batch_lib.execute_batch(idx, queries, backend="pallas")
    for q, br in zip(queries, batched):
        sr = engine.query(idx, q)
        assert sr.count == br.count
        assert np.array_equal(sr.docs, br.docs)


def test_batched_with_cache_matches(small_corpus):
    idx = builder.build(small_corpus.postings, small_corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    cache = engine.DecodeCache(capacity_ints=1 << 24)
    for _ in range(2):                   # second pass served from cache
        batched = batch_lib.execute_batch(idx, small_corpus.queries,
                                          cache=cache)
        for q, br in zip(small_corpus.queries, batched):
            sr = engine.query(idx, q)
            assert sr.count == br.count
            assert np.array_equal(sr.docs, br.docs)
    assert len(cache._store) > 0


def _n_items(idx, queries) -> int:
    """The (query, part) work items the scheduler makes of ``queries``."""
    return sum(len(v) for v in batch_lib.schedule(idx, queries).values())


def test_batched_grouping_amortizes_programs(small_corpus):
    """The scheduler must fuse work: device programs < work items."""
    idx = builder.build(small_corpus.postings, small_corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    stats = {}
    batch_lib.execute_batch(idx, small_corpus.queries, stats=stats)
    n_items = _n_items(idx, small_corpus.queries)
    assert stats["n_dispatches"] <= n_items
    assert stats["n_dispatches"] == stats["n_groups"]  # no chunk overflow


def test_batched_respects_max_group_size(small_corpus):
    idx = builder.build(small_corpus.postings, small_corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    stats = {}
    batched = batch_lib.execute_batch(idx, small_corpus.queries,
                                      max_group_size=1, stats=stats)
    assert stats["n_dispatches"] == _n_items(idx, small_corpus.queries)
    for q, br in zip(small_corpus.queries, batched):
        sr = engine.query(idx, q)
        assert sr.count == br.count
        assert np.array_equal(sr.docs, br.docs)


def test_engine_kernel_backend_matches(small_corpus):
    """USE_KERNELS routes big-ratio folds through the Pallas gallop kernel."""
    idx = builder.build(small_corpus.postings, small_corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    queries = small_corpus.queries[:4]
    baseline = [engine.query(idx, q) for q in queries]
    engine.USE_KERNELS = True
    try:
        kerneled = [engine.query(idx, q) for q in queries]
    finally:
        engine.USE_KERNELS = False
    for a, b in zip(baseline, kerneled):
        assert a.count == b.count
        assert np.array_equal(a.docs, b.docs)
