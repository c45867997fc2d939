"""Fused decode+intersect megakernel differential suite (ISSUE 7).

The megakernels (``kernels/megakernel.py``) fold a whole (J, B) stack of
decoded or packed lists into the per-row validity mask in ONE Pallas
launch, decoding candidate blocks inside the kernel.  Every test here is
differential against the staged reference — per-fold
``core/intersect.intersect_packed_batch`` (or gallop) masks ANDed exactly
as ``batch._mask_fold_scan`` does — plus the scalar ``intersect_ref``
oracle where the payload permits.  Coverage: delta modes d1–dv, FastPFOR
exception patching, sentinel padding, incoming-valid masking, inactive
fold slots, empty/single-block edges, and fused-family ceiling shapes
(k/t/c pads and B/Jp arities raised far past the payload).  Interpret
mode everywhere; the same parametrized bodies also run compiled when a
TPU backend is present (``_COMPILED``).

Also pinned here: the kernel-mode probe/override resolution of
``kernels.ops`` and the interpret-mode occupancy guard crossover of
``batch._effective_backend`` (the PR-5 fused-ceiling regression fix).
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitpack, fastpfor
from repro.core import intersect as its
from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine, source
from repro.kernels import ops as kernel_ops
from repro.kernels import megakernel

pytestmark = pytest.mark.megakernel

MODES = ["d1", "d2", "d4", "dm", "dv"]
_COMPILED = jax.default_backend() == "tpu"


def _pair(rng, m, n, overlap=0.3, universe=2**22):
    inter = np.sort(rng.choice(universe, size=max(int(m * overlap), 1),
                               replace=False))
    r = np.union1d(inter, rng.choice(universe, size=m, replace=False))
    f = np.union1d(inter, rng.choice(universe, size=n, replace=False))
    return r.astype(np.int64), f.astype(np.int64)


def _stack_payloads(grid, r_rows, *, M=256, k_pad=None, t_pad=None,
                    c_pad=None, e_pad=None, bp=None):
    """Stack a (Jp, B) grid of optional payloads into the megakernel
    operand tuple, mirroring ``batch._stack_packed``: shared pow2 pads
    (overridable to model fused-family ceilings), pad candidate ids =
    k_pad, -1-padded exception slots, inactive grid cells all-pad.
    Returns (R, pk, active) with R (Bp, M) sentinel-padded."""
    Jp, B = len(grid), len(grid[0])
    real = [p for row in grid for p in row if p is not None]
    k_pad = k_pad or its.pow2_bucket(
        max(p.widths.shape[0] for p in real), floor=1)
    t_pad = t_pad or its.pow2_bucket(
        max(int(p.flat_words.shape[0]) for p in real), floor=1)
    E = max(int(getattr(p, "exc_pos", np.zeros(0)).shape[0]) for p in real)
    if e_pad is None:
        e_pad = its.pow2_bucket(E, floor=1) if E else 0
    cands = [bitpack.candidate_block_ids(np.asarray(p.maxes), r_rows[b])
             for j, row in enumerate(grid)
             for b, p in enumerate(row) if p is not None]
    c_pad = c_pad or its.pow2_bucket(
        max(len(c) for c in cands), floor=source.CAND_FLOOR)
    Bp = bp or B
    PW = np.zeros((Jp, Bp, t_pad, 128), np.uint32)
    PWid = np.zeros((Jp, Bp, k_pad), np.int32)
    POf = np.zeros((Jp, Bp, k_pad), np.int32)
    PMx = np.zeros((Jp, Bp, k_pad), np.uint32)
    PBk = np.full((Jp, Bp, c_pad), k_pad, np.int32)
    PEp = np.full((Jp, Bp, max(e_pad, 1)), -1, np.int32)
    PEa = np.zeros((Jp, Bp, max(e_pad, 1)), np.uint32)
    active = np.zeros((Jp, Bp), bool)
    for j, row in enumerate(grid):
        for b, p in enumerate(row):
            if p is None:
                continue
            lay = bitpack.layout_np(p, k_pad, t_pad, e_pad)
            T, K = lay.words.shape[0], lay.widths.shape[0]
            PW[j, b, :T] = lay.words
            PWid[j, b, :K] = lay.widths
            POf[j, b, :K] = lay.offsets
            PMx[j, b, :K] = lay.maxes
            blk = bitpack.candidate_block_ids(np.asarray(p.maxes),
                                              r_rows[b])
            PBk[j, b] = source.pad_block_ids(blk, c_pad, k_pad)
            if e_pad:
                ne = lay.exc_pos.shape[0]
                PEp[j, b, :ne] = lay.exc_pos
                PEa[j, b, :ne] = lay.exc_add
            active[j, b] = True
    Rnp = np.full((Bp, M), its.SENTINEL, np.int32)
    for b, r in enumerate(r_rows):
        Rnp[b, : len(r)] = r
    pk = (jnp.asarray(PW), jnp.asarray(PWid), jnp.asarray(POf),
          jnp.asarray(PMx), jnp.asarray(PBk),
          jnp.asarray(PEp if e_pad else PEp[:, :, :0]),
          jnp.asarray(PEa if e_pad else PEa[:, :, :0]))
    return jnp.asarray(Rnp), pk, jnp.asarray(active)


def _staged_packed_fold(R, valid, pk, active, mode, block_rows):
    """Reference: per-fold core intersect_packed_batch masks ANDed as
    ``batch._mask_fold_scan`` does — the staged packed path."""
    out = valid
    for j in range(pk[0].shape[0]):
        hit = its.intersect_packed_batch(R, *(op[j] for op in pk),
                                         mode=mode, block_rows=block_rows)
        out = out & jnp.where(active[j][:, None], hit, True)
    return out


# --------------------------------------------------------------------------
# packed megakernel vs staged core path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_packed_fold_matches_staged_all_modes(mode, rng):
    B, Jp = 3, 2
    r_rows, grid, expects = [], [[None] * B for _ in range(Jp)], []
    for b in range(B):
        r, f0 = _pair(rng, 150, 90000)
        _, f1 = _pair(rng, 150, 60000)
        r = r[:200]
        grid[0][b] = bitpack.encode(f0, mode=mode)
        grid[1][b] = bitpack.encode(f1, mode=mode)
        r_rows.append(r)
        expects.append(np.intersect1d(np.intersect1d(r, f0), f1))
    R, pk, active = _stack_payloads(grid, r_rows)
    valid = R != its.SENTINEL
    rows = grid[0][0].block_rows
    got = kernel_ops.intersect_packed_fold(R, valid, pk, active,
                                           mode=mode, block_rows=rows)
    ref = _staged_packed_fold(R, valid, pk, active, mode, rows)
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    vals, cnt = its.compact_batch(R, got)
    for b in range(B):
        assert np.array_equal(np.asarray(vals)[b, : int(cnt[b])],
                              expects[b])


def test_packed_fold_fastpfor_exceptions(rng):
    """Exception-carrying FastPFOR blocks patch correctly inside the
    megakernel's scratch decode."""
    B = 2
    r_rows, grid = [], [[None] * B]
    for b in range(B):
        r, f = _pair(rng, 150, 150000, universe=2**26)
        pf = fastpfor.encode(f, mode="d1")
        assert int(pf.exc_pos.shape[0]) > 0
        grid[0][b] = pf
        r_rows.append(r[:200])
    R, pk, active = _stack_payloads(grid, r_rows)
    valid = R != its.SENTINEL
    rows = grid[0][0].block_rows
    got = kernel_ops.intersect_packed_fold(R, valid, pk, active,
                                           mode="d1", block_rows=rows)
    ref = _staged_packed_fold(R, valid, pk, active, "d1", rows)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_packed_fold_sentinel_padding_and_incoming_valid(rng):
    """Sentinel-padded seed slots never match; rows the incoming validity
    mask already killed stay dead (the megakernel ANDs, never revives)."""
    r, f = _pair(rng, 80, 60000)
    pf = bitpack.encode(f, mode="d1")
    R, pk, active = _stack_payloads([[pf]], [r], M=1024)  # heavy tail
    valid = (R != its.SENTINEL) & (R % 2 == 0)            # pre-killed odds
    got = np.asarray(kernel_ops.intersect_packed_fold(
        R, valid, pk, active, mode="d1", block_rows=pf.block_rows))
    assert not got[0, len(r):].any()
    assert not got[0][np.asarray(R)[0] % 2 == 1].any()
    ref = _staged_packed_fold(R, valid, pk, active, "d1", pf.block_rows)
    assert np.array_equal(got, np.asarray(ref))


def test_packed_fold_inactive_and_empty_edges(rng):
    """Inactive (j, b) slots are mask identities; a single-block list and
    an empty intersection both round-trip; an all-pad Jp slot (fused
    arity ceiling above the row's real fold count) changes nothing."""
    r, f = _pair(rng, 60, 30000)
    pf = bitpack.encode(f, mode="d1")
    evens = 2 * np.sort(rng.choice(2**20, size=3000, replace=False))
    podd = bitpack.encode(evens.astype(np.int64), mode="d1")
    tiny = np.sort(rng.choice(2**12, size=500, replace=False))
    ptiny = bitpack.encode(tiny.astype(np.int64), mode="d1")  # 1 block
    assert ptiny.num_blocks == 1
    rows = [r, evens[:64] + 1, np.asarray(tiny[:64])]
    grid = [[pf, podd, ptiny],
            [None, None, None]]                    # all-pad second slot
    R, pk, active = _stack_payloads(grid, rows)
    assert not np.asarray(active)[1].any()
    valid = R != its.SENTINEL
    brows = pf.block_rows
    got = kernel_ops.intersect_packed_fold(R, valid, pk, active,
                                           mode="d1", block_rows=brows)
    ref = _staged_packed_fold(R, valid, pk, active, "d1", brows)
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    vals, cnt = its.compact_batch(R, got)
    assert np.array_equal(np.asarray(vals)[0, : int(cnt[0])],
                          np.intersect1d(r, f))
    assert int(cnt[1]) == 0                        # disjoint: empty result
    assert np.array_equal(np.asarray(vals)[2, : int(cnt[2])], tiny[:64])


def test_packed_fold_family_ceiling_shapes(rng):
    """Fused-family ceilings: k/t/c pads and B/Jp arities raised far past
    the payload must be byte-identical to the tight-pad stack — pad
    blocks decode to SENTINEL, pad rows stay sentinel, inactive slots are
    identities (the DESIGN.md §2.12 static-geometry contract)."""
    r, f = _pair(rng, 100, 50000)
    pf = bitpack.encode(f, mode="dm")
    rows = pf.block_rows
    R1, pk1, a1 = _stack_payloads([[pf]], [r])
    tight = kernel_ops.intersect_packed_fold(
        R1, R1 != its.SENTINEL, pk1, a1, mode="dm", block_rows=rows)
    k_pad = 4 * its.pow2_bucket(pf.widths.shape[0], floor=1)
    t_pad = 2 * its.pow2_bucket(int(pf.flat_words.shape[0]), floor=1)
    grid = [[pf, None, None, None], [None] * 4, [None] * 4, [None] * 4]
    R4, pk4, a4 = _stack_payloads(
        grid, [r], M=256, k_pad=k_pad, t_pad=t_pad, c_pad=256, e_pad=8,
        bp=4)
    assert pk4[0].shape[:2] == (4, 4)
    got = kernel_ops.intersect_packed_fold(
        R4, R4 != its.SENTINEL, pk4, a4, mode="dm", block_rows=rows)
    assert np.array_equal(np.asarray(got)[0], np.asarray(tight)[0])
    assert not np.asarray(got)[1:].any() or np.array_equal(
        np.asarray(got)[1:], np.asarray(R4[1:] != its.SENTINEL))


# --------------------------------------------------------------------------
# decoded-fold megakernel
# --------------------------------------------------------------------------

def test_decoded_fold_matches_scan(rng):
    B, M, N, J = 4, 256, 1024, 3
    r = np.sort(rng.choice(1 << 20, (B, M), replace=False),
                axis=1).astype(np.int32)
    folds = np.sort(rng.choice(1 << 20, (J, B, N)), axis=-1).astype(np.int32)
    folds[0, :, :50] = r[:, 10:60]
    folds = np.sort(folds, axis=-1)
    act = rng.random((J, B)) < 0.7
    act[0, 0] = act[1, 0] = True
    valid = r % 3 != 0
    got = kernel_ops.intersect_fold_batch(
        jnp.asarray(r), jnp.asarray(valid), jnp.asarray(folds),
        jnp.asarray(act))
    ref = jnp.asarray(valid)
    for j in range(J):
        hit = its.intersect_gallop_batch(jnp.asarray(r),
                                         jnp.asarray(folds[j]))
        ref = ref & jnp.where(jnp.asarray(act[j])[:, None], hit, True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_decoded_fold_empty_stack_is_identity(rng):
    r = jnp.asarray(np.sort(rng.choice(1 << 16, (2, 128),
                                       replace=False), axis=1))
    valid = r % 2 == 0
    got = kernel_ops.intersect_fold_batch(
        r, valid, jnp.zeros((0, 2, 128), jnp.int32),
        jnp.zeros((0, 2), bool))
    assert np.array_equal(np.asarray(got), np.asarray(valid))


@pytest.mark.skipif(not _COMPILED, reason="no TPU backend: compiled-mode "
                    "Mosaic lowering unavailable (interpret covered above)")
def test_compiled_mode_matches_interpret(rng):
    r, f = _pair(rng, 100, 60000)
    pf = bitpack.encode(f, mode="d1")
    R, pk, active = _stack_payloads([[pf]], [r])
    valid = R != its.SENTINEL
    out = {}
    for interp in (True, False):
        out[interp] = np.asarray(megakernel.packed_fold_batched(
            R, valid, *pk, active, mode="d1", block_rows=pf.block_rows,
            interpret=interp))
    assert np.array_equal(out[True], out[False])


# --------------------------------------------------------------------------
# engine-level differential: megakernel path == sequential engine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fuse", [True, False])
def test_engine_pallas_megakernel_matches_sequential(fuse):
    """The full pallas program (decoded megakernel + packed megakernel +
    bitmap probes) must stay byte-identical to the sequential engine on a
    skewed corpus that exercises the skip/packed path, fused and unfused."""
    table = {2: (100.0, [1.6, 76000.0])}
    corpus = corpus_lib.synthesize(n_docs=1 << 17, n_queries=6, seed=7,
                                   table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="bp8-d1", B=0, n_parts=1)
    seq = [engine.query(idx, q) for q in corpus.queries]
    got = batch_lib.execute_batch(idx, corpus.queries, backend="pallas",
                                  fuse=fuse)
    for a, b in zip(got, seq):
        assert a.count == b.count and np.array_equal(a.docs, b.docs)


# --------------------------------------------------------------------------
# occupancy guard (PR-5 fused-ceiling interpret regression fix)
# --------------------------------------------------------------------------

def _fake_items(n, folds_each, psrc_each):
    return [batch_lib._Item(qi=i, pi=0, doc_lo=0, r=None, rsrc=None,
                            folds=[object()] * folds_each,
                            psrc=[object()] * psrc_each)
            for i in range(n)]


def test_occupancy_guard_crossover():
    """Pins the guard's crossover: a fully occupied unfused chunk stays on
    pallas; a sparse chunk under a fused family ceiling (the PR-5
    regression shape) demotes to jax in interpret mode — and only in
    interpret mode."""
    dense_key = batch_lib.GroupKey("svs", 256, 512, 0, "gallop")
    dense = _fake_items(4, folds_each=2, psrc_each=0)
    # Bp = _bucket_rows(4) = 4; slots 4·(1+2) = 12; real 4 + 8 = 12
    assert batch_lib.pallas_occupancy(dense_key, dense) == 1.0
    ceil_key = batch_lib.GroupKey(
        "svs", 256, 512, 0, "gallop",
        packed=(8, 64, 8, 0, 32, "d1"), fused=(4, 0, 4))
    sparse = _fake_items(2, folds_each=1, psrc_each=1)
    occ = batch_lib.pallas_occupancy(ceil_key, sparse)
    # Bp(2)=2 → slots 2·(1+4+4)=18, real 2+2+2=6
    assert occ == pytest.approx(6 / 18)
    assert occ < batch_lib.PALLAS_MIN_OCCUPANCY
    prev = kernel_ops.INTERPRET
    try:
        kernel_ops.INTERPRET = True
        stats: dict = {}
        assert batch_lib._effective_backend(dense_key, dense, "pallas",
                                            stats) == "pallas"
        assert batch_lib._effective_backend(ceil_key, sparse, "pallas",
                                            stats) == "jax"
        assert stats["pallas_lowocc_fallbacks"] == 1
        # jax chunks pass through untouched
        assert batch_lib._effective_backend(ceil_key, sparse, "jax",
                                            stats) == "jax"
        # compiled mode never demotes: dead TPU grid steps are cheap
        kernel_ops.INTERPRET = False
        assert batch_lib._effective_backend(ceil_key, sparse, "pallas",
                                            stats) == "pallas"
        assert stats["pallas_lowocc_fallbacks"] == 1
    finally:
        kernel_ops.INTERPRET = prev


def test_occupancy_fallback_results_identical():
    """A batch whose chunks straddle the guard threshold returns results
    byte-identical to the jax backend — the guard only reroutes engines."""
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=8, seed=3)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    stats: dict = {}
    got = batch_lib.execute_batch(idx, corpus.queries, backend="pallas",
                                  stats=stats)
    ref = batch_lib.execute_batch(idx, corpus.queries, backend="jax")
    for a, b in zip(got, ref):
        assert a.count == b.count and np.array_equal(a.docs, b.docs)


@pytest.mark.parametrize("fits", [True, False])
def test_fold_counts_follow_kernel_routing(monkeypatch, fits):
    """Every pallas fold is counted where the program routes it: in a
    kernel when its operands fit VMEM, as a VMEM fallback when they do
    not — with results identical to the jax backend either way."""
    monkeypatch.setattr(batch_lib, "PALLAS_MIN_OCCUPANCY", 0.0)
    if not fits:
        monkeypatch.setattr(kernel_ops, "decoded_fold_fits",
                            lambda *a: False)
        monkeypatch.setattr(kernel_ops, "packed_fold_fits",
                            lambda *a: False)
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=8, seed=3)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    stats: dict = {}
    got = batch_lib.execute_batch(idx, corpus.queries, backend="pallas",
                                  stats=stats)
    ref = batch_lib.execute_batch(idx, corpus.queries, backend="jax")
    for a, b in zip(got, ref):
        assert a.count == b.count and np.array_equal(a.docs, b.docs)
    assert stats["folds"] > 0
    in_kernel = stats.get("kernel_folds", 0)
    fell_back = stats.get("kernel_vmem_fallbacks", 0)
    assert in_kernel + fell_back == stats["folds"]
    assert (in_kernel, fell_back) == ((stats["folds"], 0) if fits
                                      else (0, stats["folds"]))


# --------------------------------------------------------------------------
# kernel-mode probe / override resolution
# --------------------------------------------------------------------------

def test_kernel_mode_resolution():
    prev_env = os.environ.get("REPRO_PALLAS_INTERPRET")
    prev = kernel_ops.INTERPRET
    try:
        os.environ.pop("REPRO_PALLAS_INTERPRET", None)
        probed = kernel_ops.probe_kernel_mode()
        assert probed == ("compiled" if _COMPILED else "interpret")
        assert kernel_ops.resolve_kernel_mode("auto") == probed
        os.environ["REPRO_PALLAS_INTERPRET"] = "0"
        assert kernel_ops.resolve_kernel_mode("auto") == "compiled"
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        assert kernel_ops.resolve_kernel_mode("auto") == "interpret"
        # explicit modes win over the env either way
        assert kernel_ops.resolve_kernel_mode("compiled") == "compiled"
        assert kernel_ops.resolve_kernel_mode("interpret") == "interpret"
        with pytest.raises(ValueError):
            kernel_ops.resolve_kernel_mode("fast")
        assert kernel_ops.set_kernel_mode("interpret") == "interpret"
        assert kernel_ops.INTERPRET and \
            kernel_ops.kernel_mode() == "interpret"
        assert kernel_ops.set_kernel_mode("compiled") == "compiled"
        assert not kernel_ops.INTERPRET
    finally:
        if prev_env is None:
            os.environ.pop("REPRO_PALLAS_INTERPRET", None)
        else:
            os.environ["REPRO_PALLAS_INTERPRET"] = prev_env
        kernel_ops.INTERPRET = prev


def test_import_initializes_no_backend():
    """Importing the kernel wrappers, the scheduler or the server must not
    initialize a JAX backend: on a TPU host that would take the chip as a
    side effect of an import.  Checked in a fresh interpreter."""
    import subprocess
    import sys
    code = ("import repro.kernels.ops, repro.index.batch, "
            "repro.launch.server\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
            "assert repro.kernels.ops.INTERPRET is None\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
