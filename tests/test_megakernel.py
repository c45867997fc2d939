"""Fused decode+intersect megakernel differential suite (ISSUE 7).

The megakernels (``kernels/megakernel.py``) fold a whole (J, B) stack of
decoded or packed lists into the per-row validity mask in ONE Pallas
launch, decoding candidate blocks inside the kernel.  Every test here is
differential against the staged reference — per-fold
``core/intersect.intersect_packed_batch`` (or gallop) masks ANDed exactly
as ``batch._mask_fold_scan`` does — plus the scalar ``intersect_ref``
oracle where the payload permits.  Coverage: delta modes d1–dv, FastPFOR
exception patching, sentinel padding, incoming-valid masking, inactive
fold slots, empty/single-block edges, fused-family ceiling shapes
(k/t/c pads and B/Jp arities raised far past the payload), and the row
extents: seeds of 0, 1, 127, 128, 129 and M slots in one launch walk
only their own 128-lane tiles, byte-identical to the full walk, while a
walk one tile short is caught.  Interpret
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


def _tiles(R):
    """Each seed row's real extent in 128-lane tiles, as
    ``batch.seed_tiles`` gives it from ``seed_n``."""
    n = (np.asarray(R) != its.SENTINEL).sum(axis=1)
    return jnp.asarray(-(-n // 128), jnp.int32)


# Seed lengths of one ragged launch, M the row's slots: empty, one slot,
# either side of a 128-lane tile edge, and the whole row.
def _ragged(M):
    return (0, 1, 127, 128, 129, M)


def _seed_rows(rng, lengths, M, members=None, universe=1 << 20):
    """Sorted rows of the given lengths, SENTINEL-padded to M.  About half
    of a row's values come from ``members`` (when given), the rest are
    random below ``universe``; a row's last value is ``universe + b``, in
    no fold: a planted miss that survives only where a walk skips it.
    ``valid`` has holes (values ≡ 0 mod 3 killed), is False on every
    SENTINEL and True on every planted miss."""
    R = np.full((len(lengths), M), its.SENTINEL, np.int32)
    for b, n in enumerate(lengths):
        if not n:
            continue
        k = (n - 1) // 2 if members is not None else 0
        mem = rng.choice(members, k, replace=False) if k else []
        rest = np.setdiff1d(rng.choice(universe, 2 * n, replace=False), mem)
        body = np.concatenate([mem, rng.choice(rest, n - 1 - k,
                                               replace=False)])
        R[b, :n] = np.append(np.sort(body), universe + b)
    valid = (R != its.SENTINEL) & (R % 3 != 0)
    for b, n in enumerate(lengths):
        if n:
            valid[b, n - 1] = True
    return jnp.asarray(R), jnp.asarray(valid)


def _assert_extents(launch, R, ref, short):
    """The walk over each row's real tiles, and the full walk, both equal
    ``ref`` byte for byte.  With ``short`` set, the row of 129 seed slots
    walks one tile less: its planted miss at slot 128 is left unprobed
    and survives, the one slot where the result differs from ``ref``."""
    ref = np.asarray(ref)
    tiles = _tiles(R)
    full = np.asarray(launch(megakernel.row_tiles(R)))
    assert np.array_equal(full, ref)
    if not short:
        assert np.array_equal(np.asarray(launch(tiles)), ref)
        return
    b = int(np.flatnonzero(np.asarray(tiles) == 2)[0])
    got = np.asarray(launch(tiles.at[b].add(-1)))
    assert list(zip(*np.nonzero(got != ref))) == [(b, 128)]
    assert got[b, 128] and not ref[b, 128]


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
    got = kernel_ops.intersect_packed_fold(R, valid, pk, active, _tiles(R),
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
    got = kernel_ops.intersect_packed_fold(R, valid, pk, active, _tiles(R),
                                           mode="d1", block_rows=rows)
    ref = _staged_packed_fold(R, valid, pk, active, "d1", rows)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("lengths,M,short", [
    # one short row in a long padded one (a heavy sentinel tail)
    pytest.param((80,), 1024, False, id="one-row"),
    # rows of 0, 1, 127, 128, 129 and M seed slots in one launch
    pytest.param(_ragged(512), 512, False, id="ragged"),
    # the control: the 129-slot row walks one tile short
    pytest.param(_ragged(512), 512, True, id="one-tile-short"),
])
def test_packed_fold_sentinel_padding_and_incoming_valid(rng, lengths, M,
                                                         short):
    """Sentinel-padded seed slots never match; rows the incoming validity
    mask already killed stay dead (the megakernel ANDs, never revives).
    Each row walks only its real tiles, with inactive (j, b) slots, and
    matches the staged reference and the full walk byte for byte."""
    _, f0 = _pair(rng, 80, 60000, universe=2**20)
    _, f1 = _pair(rng, 80, 40000, universe=2**20)
    R, valid = _seed_rows(rng, lengths, M, members=np.union1d(f0, f1))
    pf = [bitpack.encode(f, mode="d1") for f in (f0, f1)]
    B = len(lengths)
    grid = [[pf[0]] * B, [pf[1] if b % 2 else None for b in range(B)]]
    rows = [np.asarray(R)[b, :n] for b, n in enumerate(lengths)]
    _, pk, active = _stack_payloads(grid, rows, M=M)
    ref = _staged_packed_fold(R, valid, pk, active, "d1", pf[0].block_rows)
    assert not np.asarray(ref)[~np.asarray(valid)].any()

    def launch(tiles):
        return kernel_ops.intersect_packed_fold(
            R, valid, pk, active, tiles, mode="d1",
            block_rows=pf[0].block_rows)

    _assert_extents(launch, R, ref, short)


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
    got = kernel_ops.intersect_packed_fold(R, valid, pk, active, _tiles(R),
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
        R1, R1 != its.SENTINEL, pk1, a1, _tiles(R1), mode="dm",
        block_rows=rows)
    k_pad = 4 * its.pow2_bucket(pf.widths.shape[0], floor=1)
    t_pad = 2 * its.pow2_bucket(int(pf.flat_words.shape[0]), floor=1)
    grid = [[pf, None, None, None], [None] * 4, [None] * 4, [None] * 4]
    R4, pk4, a4 = _stack_payloads(
        grid, [r], M=256, k_pad=k_pad, t_pad=t_pad, c_pad=256, e_pad=8,
        bp=4)
    assert pk4[0].shape[:2] == (4, 4)
    got = kernel_ops.intersect_packed_fold(
        R4, R4 != its.SENTINEL, pk4, a4, _tiles(R4), mode="dm",
        block_rows=rows)
    assert np.array_equal(np.asarray(got)[0], np.asarray(tight)[0])
    assert not np.asarray(got)[1:].any() or np.array_equal(
        np.asarray(got)[1:], np.asarray(R4[1:] != its.SENTINEL))


# --------------------------------------------------------------------------
# decoded-fold megakernel
# --------------------------------------------------------------------------

@pytest.mark.parametrize("lengths,M,short", [
    # four whole rows: no SENTINEL, every tile real
    pytest.param((256,) * 4, 256, False, id="full"),
    # rows of 0, 1, 127, 128, 129 and M seed slots in one launch
    pytest.param(_ragged(512), 512, False, id="ragged"),
    # the control: the 129-slot row walks one tile short
    pytest.param(_ragged(512), 512, True, id="one-tile-short"),
])
def test_decoded_fold_matches_scan(rng, lengths, M, short):
    """Each row walks only its real tiles, with inactive (j, b) slots and
    an incoming mask with holes, and matches ``_fold_scan`` (per-fold
    gallop masks ANDed) and the full walk byte for byte."""
    B, N, J = len(lengths), 1024, 3
    r, valid = _seed_rows(rng, lengths, M)
    folds = np.empty((J, B, N), np.int32)
    for j in range(J):
        for b, n in enumerate(lengths):
            body = np.asarray(r)[b, : max(n - 1, 0)]
            hits = body[rng.random(body.size) < 0.5]    # the planted miss
            rest = np.setdiff1d(rng.choice(1 << 20, N, replace=False), hits)
            folds[j, b] = np.sort(np.concatenate([hits, rest])[:N])
    act = rng.random((J, B)) < 0.7
    act[0] = True                                  # every row folds once
    folds, act = jnp.asarray(folds), jnp.asarray(act)
    ref = kernel_ops._fold_scan(r, valid, folds, act,
                                its.intersect_gallop_batch)
    _assert_extents(
        lambda tiles: kernel_ops.intersect_fold_batch(r, valid, folds, act,
                                                      tiles),
        r, ref, short)


def test_decoded_fold_empty_stack_is_identity(rng):
    r = jnp.asarray(np.sort(rng.choice(1 << 16, (2, 128),
                                       replace=False), axis=1))
    valid = r % 2 == 0
    got = kernel_ops.intersect_fold_batch(
        r, valid, jnp.zeros((0, 2, 128), jnp.int32),
        jnp.zeros((0, 2), bool), megakernel.row_tiles(r))
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
            R, valid, *pk, active, _tiles(R), mode="d1",
            block_rows=pf.block_rows, interpret=interp))
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


def _fold_extent_run(corpus, mode):
    """Serve ``corpus`` (``conftest.make_probe_edge_corpus``) through the
    pallas megakernels in ``mode``; returns (results, sequential, stats)."""
    from repro.index import shard as shard_lib
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=8, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    stats: dict = {}
    if mode.startswith("sharded"):
        sharded = shard_lib.shard_index(idx, 2)
        stats["devices"] = len(sharded.devices)
        got = shard_lib.execute_sharded(sharded, corpus.queries,
                                        batch_size=6, backend="pallas",
                                        stats=stats)
    elif mode == "pool-arena":
        pool = source.ResidentPool()
        pool.warm(idx)
        got = batch_lib.execute_batch(idx, corpus.queries, backend="pallas",
                                      pool=pool, stats=stats)
    else:
        got = batch_lib.execute_batch(idx, corpus.queries, backend="pallas",
                                      fuse=mode == "fused", stats=stats)
    return got, seq, stats


@pytest.mark.parametrize("mode", ["unfused", "fused", "pool-arena",
                                  "sharded"])
def test_engine_fold_extents_match_sequential(probe_edge_corpus,
                                              monkeypatch, mode):
    """Seeds of 127, 128 and 129 postings, either side of a 128-lane tile
    edge (``probe_edge_corpus`` terms 8, 11, 12), folded by the megakernels
    over their own tiles only: byte-identical to the sequential engine,
    unfused, fused, from the pool's arenas and sharded over 2 of 4 host
    devices (a subprocess: the row extents then split across devices with
    the seed rows).  A skipped live tile would keep a seed's last doc,
    which is in no fold, as a phantom hit."""
    if mode == "sharded":
        import subprocess
        import sys
        tests = os.path.dirname(os.path.abspath(__file__))
        code = ("import sys\n"
                f"sys.path.insert(0, {tests!r})\n"
                "import conftest, test_megakernel as t\n"
                "t.batch_lib.PALLAS_MIN_OCCUPANCY = 0.0\n"
                "got, seq, st = t._fold_extent_run("
                "conftest.make_probe_edge_corpus(), 'sharded')\n"
                "assert st['devices'] == 2, st['devices']\n"
                "t._assert_fold_extents(got, seq, st, 'sharded')\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.path.join(os.path.dirname(tests), "src"))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        return
    monkeypatch.setattr(batch_lib, "PALLAS_MIN_OCCUPANCY", 0.0)
    _assert_fold_extents(*_fold_extent_run(probe_edge_corpus, mode), mode)


def _assert_fold_extents(got, seq, stats, mode):
    assert len(got) == len(seq)
    for a, b in zip(got, seq):
        assert a.count == b.count and np.array_equal(a.docs, b.docs)
    assert stats["kernel_folds"] == stats["folds"] > 0
    # fusion pads every seed row to the family's longest (256 slots): the
    # walk then stops short of it; unfused, M is each seed's own bucket
    assert 0 < stats["fold_tiles"] <= stats["fold_tiles_ceiling"]
    if mode != "unfused":
        assert stats["fold_tiles"] < stats["fold_tiles_ceiling"]


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
