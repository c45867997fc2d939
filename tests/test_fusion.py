"""Differential coverage for megagroup fusion + AOT warmup (ISSUE 5).

Layers:
  * fused == unfused == sequential byte-identity over {jax, pallas} ×
    {uniform, skewed} corpora, single-device and sharded at {1, 2, 4},
  * fusion edge cases: single-group batch, all-bitmap family, empty batch,
  * the bitmap probe's chunk extents: seeds of 0, 1, C - 1, C, C + 1 and M
    slots with 0 to Jb bitmaps a row and padded rows, through host
    stacking, pool arena, pool stacking, fused and sharded programs; every
    assembled seed row holds exactly ``seed_n`` non-sentinel slots,
  * the dispatch collapse itself (scheduled signatures ≫ fused dispatches
    on a mixed batch) and FusionPlan stickiness,
  * ``warmup`` compile accounting: steady-state serving after warmup
    compiles nothing.
"""

import numpy as np
import pytest

from repro.core import intersect as its
from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine, source
from repro.index import pipeline as pipe_lib
from repro.index import shard as shard_lib

pytestmark = pytest.mark.fusion


# --------------------------------------------------------------------------
# fixtures (mirrors tests/test_pipeline.py)
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
    # (skip-aware partial decode) folds through fused programs
    n_docs = 1 << 16
    table = {2: (100.0, [0.8 * (1 << 18) / n_docs,
                         38000.0 * (1 << 18) / n_docs])}
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=4, seed=7,
                                   table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="bp8-d1", B=0, n_parts=1)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


@pytest.fixture(scope="module")
def mixed():
    """Big enough mixed batch that the scheduler produces many signatures —
    the regime fusion exists for."""
    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=32, seed=11,
                                   table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


@pytest.fixture
def edges(probe_edges):
    """``probe_edge_corpus`` (conftest) built, with the probe's chunk cut
    small for the test."""
    idx = builder.build(probe_edges.postings, probe_edges.n_docs,
                        codec_name="fastpfor-d1", B=8, n_parts=2)
    seq = [engine.query(idx, q) for q in probe_edges.queries]
    return idx, probe_edges.queries, seq


def _assert_seed_rows(idx, queries, pool=None):
    """Every svs row, fused and unfused, assembles its seed as exactly
    ``seed_n`` non-sentinel slots (padded rows: none), which is what lets
    the probe skip the slots past ``seed_n``."""
    for fuse in (False, True):
        groups = batch_lib.schedule(idx, queries, pool=pool)
        if fuse:
            groups = batch_lib.fuse_groups(groups)
        for key, items in groups.items():
            if key.kind != "svs":
                continue
            R = np.asarray(batch_lib._assemble_svs(key, items, pool)[0])
            real = (R != its.SENTINEL).sum(axis=1)
            assert list(real[: len(items)]) == [it.seed_n for it in items]
            assert not real[len(items):].any()


def _assert_identical(results, seq):
    assert len(results) == len(seq)
    for got, want in zip(results, seq):
        assert got.count == want.count
        assert got.docs.dtype == want.docs.dtype
        assert np.array_equal(got.docs, want.docs)      # byte-identical


# --------------------------------------------------------------------------
# fused == unfused == sequential, single-device
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("corpus_kind", ["uniform", "skewed", "edges"])
def test_fused_matches_unfused_and_sequential(request, corpus_kind, backend):
    idx, queries, seq = request.getfixturevalue(corpus_kind)
    unfused = batch_lib.execute_batch(idx, queries, backend=backend,
                                      fuse=False)
    fused = batch_lib.execute_batch(idx, queries, backend=backend,
                                    fuse=True)
    _assert_identical(unfused, seq)
    _assert_identical(fused, seq)
    _assert_seed_rows(idx, queries)


@pytest.mark.parametrize("corpus_kind,arena,backend", [
    pytest.param("uniform", True, "jax", id="jax"),
    pytest.param("uniform", True, "pallas", id="pallas"),
    # arena=False: every group takes the pool's row-stacking path
    pytest.param("uniform", False, "jax", id="uniform-stacked-jax"),
    pytest.param("edges", True, "jax", id="edges-arena-jax"),
    pytest.param("edges", False, "jax", id="edges-stacked-jax"),
    pytest.param("edges", True, "pallas", id="edges-arena-pallas"),
])
def test_fused_pool_and_pipeline_match(request, monkeypatch, corpus_kind,
                                       arena, backend):
    idx, queries, seq = request.getfixturevalue(corpus_kind)
    if not arena:
        monkeypatch.setattr(batch_lib, "_arena_ok", lambda items: False)
    pool = source.ResidentPool()
    pool.warm(idx)
    _assert_seed_rows(idx, queries, pool)
    plan = batch_lib.FusionPlan()
    _assert_identical(
        batch_lib.execute_batch(idx, queries, backend=backend, pool=pool,
                                plan=plan), seq)
    for depth in (1, 2):
        _assert_identical(
            pipe_lib.execute_pipelined(idx, queries, batch_size=4,
                                       depth=depth, backend=backend,
                                       pool=pool, plan=plan), seq)


# --------------------------------------------------------------------------
# fused sharded fan-out
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("corpus_kind", ["uniform", "skewed", "edges"])
def test_fused_sharded_matches_sequential(request, corpus_kind, backend,
                                          n_shards):
    idx, queries, seq = request.getfixturevalue(corpus_kind)
    sharded = shard_lib.shard_index(idx, n_shards)
    out = shard_lib.execute_sharded(sharded, queries, batch_size=4, depth=2,
                                    backend=backend, fuse=True)
    _assert_identical(out, seq)


def test_sharded_fused_collapses_dispatches(mixed):
    idx, queries, seq = mixed
    sharded = shard_lib.shard_index(idx, 2)
    fused_stats: dict = {}
    out = shard_lib.execute_sharded(sharded, queries, batch_size=32,
                                    depth=2, stats=fused_stats)
    _assert_identical(out, seq)
    unfused_stats: dict = {}
    shard_lib.execute_sharded(sharded, queries, batch_size=32, depth=2,
                              fuse=False, stats=unfused_stats)
    assert fused_stats["n_dispatches"] * 4 <= unfused_stats["n_dispatches"]


# --------------------------------------------------------------------------
# edge cases
# --------------------------------------------------------------------------

def test_fused_empty_batch(uniform):
    idx, _, _ = uniform
    assert batch_lib.execute_batch(idx, [], fuse=True) == []
    assert pipe_lib.execute_pipelined(idx, [], batch_size=8, fuse=True) == []


def test_fused_single_group_batch(uniform):
    """A batch whose schedule yields one group still round-trips through
    fusion (the fused key coarsens algo/arities but stays one program)."""
    idx, queries, seq = uniform
    stats: dict = {}
    out = batch_lib.execute_batch(idx, [queries[0]], fuse=True, stats=stats)
    _assert_identical(out, seq[:1])
    assert stats["n_fused_groups"] == stats["n_dispatches"]


def test_fused_all_bitmap_family():
    """Dense-only index: every query is an all-bitmap item; fusion merges
    the bitmap groups into one family program per batch."""
    n_docs = 1 << 12
    rng = np.random.default_rng(5)
    postings = [np.sort(rng.choice(n_docs, n_docs // 4, replace=False))
                for _ in range(3)]
    idx = builder.build(postings, n_docs, codec_name="bp-d1", B=16,
                        n_parts=2)
    assert all(tp.kind == "bitmap" for p in idx.parts
               for tp in p.terms.values())
    queries = [[0, 1], [1, 2], [0, 1, 2], [2]]
    seq = [engine.query(idx, q) for q in queries]
    for fuse in (False, True):
        _assert_identical(
            batch_lib.execute_batch(idx, queries, fuse=fuse), seq)
    stats: dict = {}
    batch_lib.execute_batch(idx, queries, fuse=True, stats=stats)
    assert stats["n_dispatches"] == 1           # one bitmap family program


def test_fused_mixed_words_and_missing_bitmaps(uniform):
    """Queries of different bitmap arity (including none) fuse into one svs
    family: missing probe slots gather the all-ones identity."""
    idx, queries, seq = uniform
    pool = source.ResidentPool()
    pool.warm(idx)
    _assert_identical(
        batch_lib.execute_batch(idx, queries, pool=pool, fuse=True), seq)


def test_fused_composite_zero_length_tail():
    """Composite lists at exact block multiples carry a zero-length varint
    tail; the decoded serving path and the fused family ceilings must both
    stay inert to the empty-tail container (ISSUE 8 bugfix guard)."""
    from repro.core import composite
    per = composite.DEFAULT_ROWS * 128
    n_docs = 1 << 14
    rng = np.random.default_rng(13)
    postings = [np.sort(rng.choice(n_docs, per, replace=False)),       # tail 0
                np.sort(rng.choice(n_docs, per + 3, replace=False)),   # tail 3
                np.sort(rng.choice(n_docs, 200, replace=False))]       # no head
    idx = builder.build(postings, n_docs, codec_name="composite-d1", B=0,
                        n_parts=1, varint_tail_below=0)
    payloads = [tp.payload for tp in idx.parts[0].terms.values()]
    assert payloads[0].tail.n == 0 and payloads[1].tail.n == 3
    assert payloads[2].head is None
    queries = [[0, 1], [0, 2], [1, 2], [0, 1, 2]]
    seq = [engine.query(idx, q) for q in queries]
    for fuse in (False, True):
        _assert_identical(
            batch_lib.execute_batch(idx, queries, fuse=fuse), seq)


def test_fused_mixed_codec_families_one_batch():
    """An autotuned index mixes varint/composite/bitpack payloads in one
    batch; sentinel padding from the decoded sources must stay inert
    through the fused family ceilings on both backends — 2^32-range values
    sit right under the int32 sentinel, the regime where a padding bug
    would surface as phantom hits."""
    n_docs = 1 << 14
    rng = np.random.default_rng(17)
    postings = [np.sort(rng.choice(n_docs, n, replace=False))
                for n in (60, 300, 1100, 5000, 9000)]
    idx = builder.build(postings, n_docs, codec_name="auto", B=0, n_parts=1)
    fams = {type(tp.payload).__name__ for p in idx.parts
            for tp in p.terms.values() if tp.kind == "list"}
    assert len(fams) >= 2                       # genuinely mixed families
    queries = [[0, 4], [1, 3], [2, 4], [0, 1, 2], [3, 4], [0, 1, 2, 3, 4]]
    seq = [engine.query(idx, q) for q in queries]
    for backend in ("jax", "pallas"):
        for fuse in (False, True):
            _assert_identical(
                batch_lib.execute_batch(idx, queries, backend=backend,
                                        fuse=fuse), seq)


# --------------------------------------------------------------------------
# the dispatch collapse + plan stickiness
# --------------------------------------------------------------------------

def test_fusion_collapses_dispatch_count(mixed):
    idx, queries, seq = mixed
    unfused_stats: dict = {}
    _assert_identical(batch_lib.execute_batch(idx, queries, fuse=False,
                                              stats=unfused_stats), seq)
    fused_stats: dict = {}
    _assert_identical(batch_lib.execute_batch(idx, queries, fuse=True,
                                              stats=fused_stats), seq)
    assert fused_stats["n_sched_groups"] == unfused_stats["n_groups"]
    # the ISSUE 5 gate: ≥ 4× fewer device dispatches on a mixed batch
    assert fused_stats["n_dispatches"] * 4 <= unfused_stats["n_dispatches"]


def test_fusion_plan_ceilings_are_sticky(mixed):
    idx, queries, _ = mixed
    plan = batch_lib.FusionPlan()
    full = batch_lib.fuse_groups(batch_lib.schedule(idx, queries),
                                 plan=plan)
    # a later, narrower batch reuses the full batch's (sticky) ceilings,
    # so its fused keys — and therefore compiled programs — are a subset
    sub = batch_lib.fuse_groups(batch_lib.schedule(idx, queries[:3]),
                                plan=plan)
    assert set(sub).issubset(set(full))


def test_fused_key_shape_contains_members(mixed):
    idx, queries, _ = mixed
    groups = batch_lib.schedule(idx, queries)
    fused = batch_lib.fuse_groups(dict(groups))
    assert len(fused) < len(groups)
    for fkey in fused:
        assert fkey.fused is not None
        members = [k for k in groups
                   if k.kind == fkey.kind
                   and ((k.packed is None) == (fkey.packed is None))]
        for k in members:
            assert fkey.m_bucket >= k.m_bucket
            assert fkey.n_bucket >= k.n_bucket
            assert fkey.words >= k.words
    # every scheduled item lands in exactly one fused group
    assert (sum(len(v) for v in fused.values())
            == sum(len(v) for v in groups.values()))


# --------------------------------------------------------------------------
# AOT warmup
# --------------------------------------------------------------------------

def test_warmup_then_steady_state_never_compiles(mixed):
    idx, queries, seq = mixed
    pool = source.ResidentPool()
    pool.warm(idx)
    plan = batch_lib.FusionPlan()
    wu = batch_lib.warmup(idx, queries, plan=plan, batch_size=8, pool=pool)
    assert wu["n_signatures"] > 0
    assert wu["passes"] >= 2                    # ran to the fixed point
    stats: dict = {}
    out = []
    for lo in range(0, len(queries), 8):
        out.extend(batch_lib.execute_batch(idx, queries[lo: lo + 8],
                                           pool=pool, plan=plan,
                                           stats=stats))
    _assert_identical(out, seq)
    assert stats.get("n_compiles", 0) == 0


def test_warmup_synthesizes_queries_when_none_given(uniform):
    idx, _, _ = uniform
    qs = batch_lib.synth_warmup_queries(idx, 8, seed=3)
    assert len(qs) == 8
    for q in qs:
        assert len(q) >= 1
        out = engine.query(idx, q)              # every query is answerable
        assert out.count >= 0
    plan = batch_lib.FusionPlan()
    wu = batch_lib.warmup(idx, None, plan=plan, batch_size=8)
    assert wu["n_signatures"] > 0
