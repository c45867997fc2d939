"""Codec-breadth + storage-autotuner coverage (ISSUE 8).

Layers:
  * property-style roundtrip sweep — empty / single / dense-run / 32-bit
    extreme inputs across every codec family and delta mode, host decode
    and device decode both,
  * StreamVByte Pallas kernel vs host reference differential,
  * cost-model autotuner unit behavior (short → host-decoded varint,
    long → skip-capable bitpack; a zero-dispatch table — a compiled-TPU
    profile — flips mid lists to composite, showing the table is the
    platform knob),
  * autotuned-vs-all-bitpack byte-identity over {jax, pallas} × {uniform,
    skewed} × shards {1, 2}, fused and unfused.
"""

import numpy as np
import pytest

from repro.core import codecs, composite, streamvbyte
from repro.core.deltas import MODES
from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine

pytestmark = pytest.mark.codec

FAMILIES = ["bp", "bp8", "fastpfor", "streamvbyte", "composite"]
DELTA_MODES = [m for m in MODES if m != "none"]


def _cases(rng):
    """Adversarial value sets: the block/tail/width boundaries every codec
    layout has to get right."""
    yield "empty", np.zeros(0, np.int64)
    yield "single", np.array([7], np.int64)
    yield "single_zero", np.array([0], np.int64)
    yield "dense_run", np.arange(1000, dtype=np.int64)
    yield "block_exact", np.arange(0, 2048, 2, dtype=np.int64)  # 1024 ints
    yield "block_plus_one", np.arange(0, 2050, 2, dtype=np.int64)
    yield "lane_tail", np.sort(rng.choice(1 << 20, 129, replace=False))
    yield ("extremes_32bit",
           np.array([0, 1, 2**31 - 1, 2**32 - 2, 2**32 - 1], np.int64))
    yield ("wide_gaps",
           np.cumsum(rng.integers(1, 1 << 24, 300)).astype(np.int64))


@pytest.mark.parametrize("fam", FAMILIES)
@pytest.mark.parametrize("mode", DELTA_MODES)
def test_roundtrip_sweep(fam, mode):
    if fam == "composite" and mode != "d1":
        pytest.skip("composite registered for d1 only")
    c = codecs.get_codec(f"{fam}-{mode}")
    rng = np.random.default_rng(5)
    for label, vals in _cases(rng):
        enc = c.encode(vals)
        got = np.asarray(c.decode_np(enc))[: len(vals)]
        np.testing.assert_array_equal(
            got.astype(np.int64), vals, err_msg=f"{fam}-{mode}/{label}")


@pytest.mark.parametrize("fam", FAMILIES + ["varint"])
def test_device_decode_matches_host(fam):
    c = codecs.get_codec("varint" if fam == "varint" else f"{fam}-d1")
    rng = np.random.default_rng(9)
    for label, vals in _cases(rng):
        enc = c.encode(vals)
        host = np.asarray(c.decode_np(enc))[: len(vals)]
        dev = np.asarray(c.decode(enc))[: len(vals)]
        np.testing.assert_array_equal(dev.astype(np.int64),
                                      host.astype(np.int64),
                                      err_msg=f"{fam}/{label}")


def test_streamvbyte_control_stream_layout():
    # 1/2/3/4-byte values land in the advertised 2-bit control codes
    vals = np.array([3, 300, 70000, 2**25], np.int64)
    sl = streamvbyte.encode(vals, mode="none")
    codes = [(int(sl.ctrl[0, 0]) >> (2 * i)) & 3 for i in range(4)]
    assert codes == [0, 1, 2, 3]
    np.testing.assert_array_equal(streamvbyte.decode_np(sl)[:4], vals)


@pytest.mark.parametrize("mode", DELTA_MODES)
def test_streamvbyte_pallas_kernel_matches_host(mode):
    from repro.kernels import svb_decode
    rng = np.random.default_rng(11)
    for n, rows, kernel in ((1, 1, False), (300, 1, True), (1024, 1, False),
                            (4096, 1, False), (3000, 8, True)):
        vals = np.sort(rng.choice(1 << 28, n, replace=False)).astype(np.int64)
        sl = streamvbyte.encode(vals, mode=mode, block_rows=rows)
        host = streamvbyte.decode_np(sl)
        dev = np.asarray(svb_decode.decode_bucketed(sl))[: sl.n]
        np.testing.assert_array_equal(dev.astype(np.int64), host)
        if not kernel:
            continue
        seeds = np.concatenate([[0], sl.maxes[:-1]]).astype(np.uint32)
        kern = np.asarray(svb_decode.unpack_svb_blocks(
            sl.ctrl, sl.data, sl.doffs, seeds, mode=mode, block_rows=rows,
            interpret=True)).reshape(-1)[: sl.n]
        np.testing.assert_array_equal(kern.astype(np.int64), host)


def test_composite_head_tail_split():
    per = composite.DEFAULT_ROWS * 128
    rng = np.random.default_rng(3)
    for n in (per - 1, per, per + 1, 3 * per + 17):
        vals = np.sort(rng.choice(1 << 26, n, replace=False)).astype(np.int64)
        cl = composite.encode(vals)
        assert cl.n_head == (n // per) * per
        assert cl.tail.n == n - cl.n_head
        np.testing.assert_array_equal(composite.decode_np(cl), vals)


# --------------------------------------------------------------------------
# autotuner unit behavior
# --------------------------------------------------------------------------

def test_autotune_dispatch_cost_drives_choice():
    cm = builder.CostModel.resolve(None)
    rng = np.random.default_rng(0)
    short = np.sort(rng.choice(1 << 18, 100, replace=False))
    long = np.sort(rng.choice(1 << 22, 50000, replace=False))
    name_s, skip_s = builder.autotune_choice(short, 1 << 18, cm)
    name_l, skip_l = builder.autotune_choice(long, 1 << 22, cm)
    # this container: device dispatch ~200 us/list hands short lists to the
    # host-decoded byte codecs; long lists stay skip-capable bitpack
    assert name_s in ("varint", "composite-d1") and not skip_s
    assert name_l == "bp-d1" and skip_l


def test_autotune_zero_dispatch_table_prefers_composite():
    # a compiled-TPU-shaped table (dispatch ~free, space dominant) flips
    # mid-length lists to the bitpack-head + varint-tail composite — the
    # cost table is the per-platform knob, not a hardcoded policy
    cm = builder.CostModel.resolve({
        "decode_ns_per_int": {"bp-d1": 1.0, "bp8-d1": 1.0,
                              "streamvbyte-d1": 1.1, "varint": 3.0},
        "dispatch_ns_per_list": {},
        "gallop_ns_per_probe": 10.0,
        "space_ns_per_byte": 50.0,
    })
    rng = np.random.default_rng(1)
    n = 1100                    # one full 1024-int head block + short tail
    seg = np.sort(rng.choice(1 << 22, n, replace=False))
    name, skip_ok = builder.autotune_choice(seg, 1 << 22, cm)
    assert name == "composite-d1" and not skip_ok


def test_cost_model_resolve_sources(tmp_path):
    import json
    table = {"decode_ns_per_int": {"bp-d1": 2.0},
             "dispatch_ns_per_list": {"bp-d1": 5.0},
             "gallop_ns_per_probe": 7.0}
    p = tmp_path / "cost.json"
    p.write_text(json.dumps(table))
    for cm in (builder.CostModel.resolve(table),
               builder.CostModel.resolve(str(p))):
        assert cm.decode_ns("bp") == 2.0
        assert cm.dispatch_ns("bp") == 5.0
        assert cm.gallop_ns_per_probe == 7.0
    assert builder.CostModel.resolve(None).decode_ns_per_int  # shipped table


def test_skip_ok_false_forces_decoded_path():
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=6, seed=21)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="bp8-d1", B=0, n_parts=1)
    seq = [engine.query(idx, q) for q in corpus.queries]
    for part in idx.parts:            # flip every list off the skip path
        for tp in part.terms.values():
            tp.skip_ok = False
    stats: dict = {}
    out = batch_lib.execute_batch(idx, corpus.queries, skip=True, stats=stats)
    for a, b in zip(out, seq):
        assert a.count == b.count and np.array_equal(a.docs, b.docs)
    assert stats.get("skip_folds", 0) == 0


# --------------------------------------------------------------------------
# autotuned vs all-bitpack differential matrix
# --------------------------------------------------------------------------

def _corpora():
    uniform = corpus_lib.synthesize(n_docs=1 << 14, n_queries=8, seed=33)
    table = {2: (100.0, [0.8, 1500.0])}     # tiny rare + long frequent term
    skewed = corpus_lib.synthesize(n_docs=1 << 14, n_queries=8, seed=7,
                                   table=table)
    return {"uniform": uniform, "skewed": skewed}


@pytest.mark.parametrize("profile", ["uniform", "skewed"])
@pytest.mark.parametrize("n_parts", [1, 2])
def test_autotuned_matches_all_bitpack(profile, n_parts):
    corpus = _corpora()[profile]
    auto = builder.build(corpus.postings, corpus.n_docs, codec_name="auto",
                         B=16, n_parts=n_parts)
    bp = builder.build(corpus.postings, corpus.n_docs, codec_name="bp-d1",
                       B=16, n_parts=n_parts, varint_tail_below=0)
    sa, sb = auto.stats(), bp.stats()
    assert sa["bytes_per_int"] <= sb["bytes_per_int"]
    seq = [engine.query(bp, q) for q in corpus.queries]
    for backend in ("jax", "pallas"):
        for fuse in (True, False):
            out = batch_lib.execute_batch(
                auto, corpus.queries, backend=backend,
                plan=batch_lib.FusionPlan() if fuse else None, fuse=fuse)
            for a, b in zip(out, seq):
                assert a.count == b.count
                assert np.array_equal(np.asarray(a.docs), np.asarray(b.docs))


@pytest.mark.slow
@pytest.mark.parametrize("profile", ["uniform", "skewed"])
def test_autotuned_matches_all_bitpack_sharded(profile):
    from repro.index import shard as shard_lib
    corpus = _corpora()[profile]
    seq = None
    for codec_kw in (dict(codec_name="auto"),
                     dict(codec_name="bp-d1", varint_tail_below=0)):
        sharded = builder.build_sharded(
            corpus.postings, corpus.n_docs, n_shards=2, B=16, **codec_kw)
        out = shard_lib.execute_sharded(sharded, corpus.queries)
        if seq is None:
            seq = out
            continue
        for a, b in zip(out, seq):
            assert a.count == b.count
            assert np.array_equal(np.asarray(a.docs), np.asarray(b.docs))
