"""Batched query execution: shape-bucketed scheduling + device-side SvS.

The sequential engine (``repro.index.engine``) answers one query at a time
and bounces candidates to host between every SvS fold — exactly the dispatch
overhead the paper warns fast decoders drown in.  This module keeps whole
query *batches* inside the vectorized regime:

  1. **Schedule.** Every (query, index-part) work item is assigned a shape
     signature: (pow2 bucket of the shortest list M, pow2 bucket of the
     longest fold list N, bitmap word count, intersect algorithm, packed
     signature).  Fold terms resolve through the posting-source layer
     (``repro.index.source``): short lists decode (and cache), long
     skip-capable lists stay *packed* and carry a batch-uniform layout —
     words/widths/offsets/maxes buckets plus the host-precomputed candidate
     block ids — so compressed long lists are never fully decoded in the
     batch regime either.  Term counts are *not* part of the signature —
     queries of different arity merge into one program, padded to the
     group's max fold/probe count with masked no-op folds and all-ones
     bitmap rows (probe identities) — and the batch dimension is bucketed
     on a ×1.5 ladder, so the compile count stays O(log² n_docs · log B)
     overall.
  2. **Fuse (megagroups).** A realistic mixed batch spans dozens of shape
     signatures, and at ~60µs/arg of host jit-dispatch cost the *number of
     device programs per batch* becomes the serving bottleneck once operand
     assembly is arena-gathered (DESIGN.md §2.10).  ``fuse_groups``
     therefore coarsens compatible GroupKeys into signature **families** —
     same kind and packed block geometry; M/N/W/packed pads raised to the
     family ceiling; fold/probe arity ceilings pow2-bucketed — and
     concatenates their items along the batch-row axis, so one batch
     launches O(#families) ≈ O(1) fused programs instead of one per
     signature.  Fusion is sound because group programs are row-independent
     and padding is inert (module invariants below): a row assembled into a
     wider slot gathers sentinel/identity filler that never contributes to
     its result.  Fused programs force ``algo="gallop"``: the coarsened
     M/N make the tiled ratio rule meaningless, and the lock-step tile
     walk loses its data-dependent early exit entirely at family ceilings
     while galloping stays O(M log N) per row.  A sticky ``FusionPlan``
     keeps family ceilings monotone across batches so fused signatures
     converge, and ``warmup`` precompiles the family ladder ahead of the
     first batch (AOT signature warmup — steady-state serving never
     compiles).
  3. **Execute.** Each (fused) group runs as a *single* device program: the
     batch of shortest lists (B, M) is intersected with the stacked decoded
     fold lists (J, B, N) by a ``lax.scan`` of vmapped intersects, then
     with the stacked *packed* folds (tuple of (Jp, B, ...) layout arrays,
     each step a skip-aware partial decode of candidate blocks only), then
     the surviving candidates are probed against the stacked bitmap terms
     (J_b, B, W) — candidates never round-trip to host between terms.
     Every step ANDs its match mask into one running validity mask over the
     *original* sorted seed buffer instead of compacting between folds:
     compaction never shrank the (static) shapes, but its cumsum+scatter
     was the single most expensive op in the program, and mask-folding is
     what keeps the fused ceilings affordable.  Fold order is
     decoded-then-packed, which is safe because set intersection commutes
     and every mask is computed against the same sorted seed row.
     All-bitmap queries reduce to a batched AND + popcount.  Without a
     pool, stacking happens host-side in numpy (one device transfer per
     operand); with a ``source.ResidentPool`` the operands are
     device-resident and each one assembles as a single row-arena gather —
     no decode, no padding memcpy, no H2D transfer, and no per-row
     dispatch cost (DESIGN.md §2.8).
  4. **Aggregate.** Per-item results are re-assembled per query in index-part
     order, matching the sequential engine byte for byte.  Device results
     arrive masked-but-uncompacted; the host extracts the valid (still
     sorted) entries per row.

This module is DESIGN.md §2.7 (scheduler + group-key scheme); §2.8 covers
the resident/pipelined serving built on it, §2.9 the sharded fan-out, and
§2.10 megagroup fusion + warmup.  Invariants callers rely on:

  * **Group-signature stability** — ``GroupKey`` describes operand
    *shapes* only (pow2 buckets, block geometry, algorithm).  Residency,
    arenas, caches, and sharding change where a row lives or which device
    computes it, never its shape, so every serving mode compiles the same
    per-signature programs and the compile count stays bounded.  Fusion
    preserves this: a fused key is just a GroupKey at family-ceiling
    buckets, and the sticky ``FusionPlan`` makes those ceilings monotone
    so fused signatures converge to a fixed point.  The sharded executor
    additionally relies on group programs being row-independent (the only
    scanned axis is the fold axis), which is what lets it split the row
    axis across devices unchanged.
  * **Byte-identical aggregation** — per-query results concatenate in
    part order (items carry their part ordinal; ``collect_batch`` sorts
    by it), preserving global doc-id sortedness, so batched ==
    pipelined == sharded == sequential, element for element.
  * **Padding is inert** — padded batch rows, masked no-op folds,
    identity bitmap rows, and all-pad packed layouts never contribute to
    any active row's result.

Launch and collect are split (``launch_groups`` dispatches every group
program and returns a ``PendingBatch`` of un-materialized device results;
``collect_batch`` blocks and aggregates) so ``repro.index.pipeline`` can
overlap host scheduling of batch k+1 with device execution of batch k.
``execute_batch`` composes the two and is byte-identical to the sequential
engine.  The candidate buffer is donated to the device program — it is
freshly assembled per dispatch and never reused, so XLA can reuse its
pages for the output.

Algorithm choice: under ``vmap`` the tiled merge runs lock-step across the
batch — the slowest row sets the step count and its data-dependent early
exit is lost — so the batched dispatcher biases much harder toward galloping
than the sequential ratio rule (``BATCH_TILED_MAX_RATIO`` vs the paper's
50×; re-derived in ``benchmarks/bench_engine.py``), and fused megagroup
programs force galloping outright (see ``fuse_groups``).

Backends: ``backend="jax"`` uses the jnp searchsorted / tile-merge paths from
``core.intersect``; ``backend="pallas"`` routes every fold through the Pallas
galloping kernel (``kernels.ops.intersect_gallop_batch``).
"""

from __future__ import annotations

import dataclasses
import time
from collections import defaultdict
from functools import lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bitmap as bm
from repro.core import codecs as codec_lib
from repro.core import intersect as its
from repro.index import source
from repro.index.builder import HybridIndex
from repro.index.engine import QueryResult

MAX_GROUP_SIZE = 128          # hard cap on items per device program
GROUP_INT_BUDGET = 1 << 25    # cap operand ints per program: B·(J·N+M+J_b·W)
BATCH_TILED_MAX_RATIO = 4.0   # vmapped tile-merge loses early exit; see above
PALLAS_MIN_OCCUPANCY = 0.5    # interpret-mode kernel guard; see below

# Interpret-mode Pallas executes every grid step on the host, so its cost
# scales with the PADDED grid (Bp·(1+J+Jp) fused-family ceiling slots), not
# the real payload — a sparsely occupied fused chunk can pay several times
# its useful work in dead steps (the PR-5 fused-ceiling regression).  When
# the kernels run in interpret mode and a chunk's occupancy (real rows +
# folds over padded grid slots) falls below PALLAS_MIN_OCCUPANCY, the
# launcher routes that one program through the jax backend instead —
# byte-identical results (the mask-fold contract is backend-independent),
# counted in stats["pallas_lowocc_fallbacks"].  Compiled mode skips the
# guard: dead TPU grid steps retire in microseconds and kernel residency
# is worth keeping (DESIGN.md §2.12).

# Donating the candidate buffer lets XLA alias its pages for the output; it
# is always freshly stacked per dispatch so nothing aliases it on the host.
_DONATE_CANDIDATES = (0,)


@dataclasses.dataclass(frozen=True)
class GroupKey:
    """Shape signature shared by all work items of one device program.
    Term counts are deliberately NOT part of the key: queries of different
    arity merge into one program, padded to the group's max fold/probe count
    with masked no-op folds and all-ones bitmap rows (probe identities).
    Packed folds replace the fold-length bucket with their block-layout
    buckets: (k_pad blocks, t_pad word rows, c_pad candidate blocks,
    e_pad exceptions, block_rows, delta mode).

    ``fused`` is set only on megagroup keys produced by ``fuse_groups``:
    the pow2-bucketed fold/probe arity ceilings — ('svs': (J, Jb, Jp),
    'bitmap': (J,)) — which a fused program pins so its signature does not
    drift with the arity mix of each batch.  Scheduled (unfused) keys
    leave it None and derive arities from their items, as before."""
    kind: str              # 'svs' (≥1 list term) | 'bitmap' (all-bitmap)
    m_bucket: int          # candidate buffer length M
    n_bucket: int          # decoded fold-list pad length N
    words: int             # bitmap word count W (0 when no bitmaps)
    algo: str              # 'tiled' | 'gallop' | '-'
    packed: tuple | None = None   # (k_pad, t_pad, c_pad, e_pad, rows, mode)
    fused: tuple | None = None    # megagroup arity ceilings (see above)


@dataclasses.dataclass
class _Item:
    qi: int                # query index within the submitted batch
    pi: int                # index-part ordinal (aggregation order)
    doc_lo: int
    r: object = None                      # (M,) seed: np (host) | jnp (pool)
    folds: list | None = None             # host: J × (N,) np
                                          # pool: J × DecodedSource
    psrc: list | None = None              # Jp × (layout, blk) — layout is
                                          # the self-padded np PackedLayout
                                          # (host) or the PackedSource
                                          # itself (pool; arena-assembled);
                                          # blk is the RAW candidate block
                                          # id list (padded at stack time to
                                          # the launching key's c_pad/k_pad,
                                          # which fusion may have raised)
    bm_words: np.ndarray | None = None    # host: (J_b, W) bitmap word rows
    bm_dev: list | None = None            # pool: J_b × (W,) resident rows
    bm_keys: list | None = None           # pool: J_b × pool keys (arenas)
    rsrc: object = None                   # pool: seed DecodedSource


# every jitted stacker ever created, so _compile_count can see their
# caches too (the arena-fallback path compiles stack programs mid-serving)
_STACKERS: list = []


@lru_cache(maxsize=None)
def _stacker(n: int):
    """Jitted n-ary row stack.  ``jnp.stack`` on a list dispatches one
    eager expand_dims per row — ~45µs each on a host backend, which made
    operand assembly the dominant serving cost; a jitted stacker is one
    dispatch for the whole stack (~8× cheaper at 128 rows).  Memoized per
    arity; jit itself re-specializes per row shape/dtype, and with inputs
    committed to one device the stack runs (and its result stays) there —
    which is what keeps per-shard slices on their own devices."""
    fn = jax.jit(lambda *xs: jnp.stack(xs))
    _STACKERS.append(fn)
    return fn


def _stack_rows(rows: list) -> jnp.ndarray:
    return _stacker(len(rows))(*rows)


def _bucket_rows(b: int) -> int:
    """Batch-dim bucket: ~×1.5 geometric ladder (1,2,3,4,6,9,13,19,28,…).
    Bounds the compile count per signature at O(log B) while wasting at
    most 1/3 of rows on padding (a pow2 ladder wastes up to 2×, which
    shows up directly as lost throughput on small groups)."""
    size = 1
    while size < b:
        size = size * 3 // 2 if size >= 2 else size + 1
    return size


def _extend_np(vals: np.ndarray, size: int) -> np.ndarray:
    return vals if vals.shape[0] == size else its.pad_to(vals, size)


def _extend_words(w: np.ndarray, size: int) -> np.ndarray:
    """Zero-extend a bitmap word row to a (possibly fused) W bucket.  Zeros
    are inert both ways: probes never index past the row's real doc span,
    and the all-bitmap AND meets a zero extension on every real term, so
    the popcount contribution is 0."""
    if w.shape[0] == size:
        return w
    out = np.zeros(size, np.uint32)
    out[: w.shape[0]] = w
    return out


def _extend_words_dev(row: jnp.ndarray, size: int) -> jnp.ndarray:
    if row.shape[0] == size:
        return row
    return jnp.concatenate(
        [row, jnp.zeros(size - row.shape[0], jnp.uint32)])


def schedule(index: HybridIndex, queries: list[list[int]], cache=None,
             skip: bool = True, stats: dict | None = None,
             pool: "source.ResidentPool | None" = None
             ) -> dict[GroupKey, list[_Item]]:
    """Bucket every (query, part) work item by shape signature.  Terms
    resolve through the posting-source layer here (host side, optionally
    cached): short lists decode, long skip-capable lists keep their packed
    layout plus host-searched candidate block ids.  With a ResidentPool the
    items carry *references to resident device buffers*; without one they
    carry host numpy arrays.  Everything downstream of this point is device
    programs over stacked operands."""
    codec = codec_lib.get_codec(index.codec_name)
    # sharded serving hands in one device-pinned pool per part (an object
    # with .for_part); plain serving hands in a single pool or None
    pool_of = (pool.for_part if hasattr(pool, "for_part")
               else (lambda pi: pool))
    groups: dict[GroupKey, list[_Item]] = defaultdict(list)
    for qi, term_ids in enumerate(queries):
        for pi, part in enumerate(index.parts):
            pool = pool_of(pi)
            tps = [part.terms[t] for t in term_ids]
            if any(tp.kind == "empty" for tp in tps):
                continue
            pairs = [(t, tp) for t, tp in zip(term_ids, tps)
                     if tp.kind == "list"]
            pairs.sort(key=lambda p: p[1].n)
            bm_pairs = [(t, tp) for t, tp in zip(term_ids, tps)
                        if tp.kind == "bitmap"]
            W = len(bm_pairs[0][1].payload) if bm_pairs else 0
            bm_words = bm_dev = bm_keys = None
            if bm_pairs:
                if pool is not None:
                    # (key, host row) pairs: the arena assembler must not
                    # depend on store residency (tiny pools evict between
                    # schedule and assembly)
                    bm_keys = [(("bm", part.uid, t), np.asarray(tp.payload))
                               for t, tp in bm_pairs]
                    bm_dev = [pool.stage_bitmap(k, w) for k, w in bm_keys]
                else:
                    bm_words = np.stack([tp.payload for _, tp in bm_pairs])
            if not pairs:
                key = GroupKey("bitmap", 0, 0, W, "-")
                groups[key].append(_Item(qi, pi, part.doc_lo,
                                         bm_words=bm_words, bm_dev=bm_dev,
                                         bm_keys=bm_keys))
                continue
            seed_t, seed_tp = pairs[0]
            seed = source.resolve(part, seed_t, seed_tp, codec, cache=cache,
                                  r_count=None, stats=stats, pool=pool)
            seed_np = (seed.vals_np if seed.vals_np is not None
                       else np.asarray(seed.vals))
            M = seed_np.shape[0]
            dec, packed = [], []
            for t, tp in pairs[1:]:
                src = source.resolve(part, t, tp, codec, cache=cache,
                                     r_count=seed_tp.n, skip=skip,
                                     stats=stats, pool=pool)
                if isinstance(src, source.PackedSource):
                    packed.append((t, tp, src))
                else:
                    dec.append(src)
            psig, psrc = None, None
            if packed:
                # stacking along the fold axis needs one block geometry:
                # keep the longest fold's (block_rows, mode), decode the
                # rare mismatch (adaptive block sizing on mid-length lists)
                ref = max(packed, key=lambda p: p[2].n)[2]
                rows, mode = ref.block_rows, ref.mode
                keep, demote = [], []
                for p in packed:
                    same = (p[2].block_rows == rows and p[2].mode == mode)
                    (keep if same else demote).append(p)
                for t, tp, _ in demote:
                    # cache=None / pool=None: a demoted long list must not
                    # evict the int-budgeted stores' hot short lists — and
                    # staging it resident would permanently win over
                    # want_skip, disabling its block-max skip path over a
                    # one-off grouping accident
                    src = source.resolve(part, t, tp, codec, cache=None,
                                         skip=False, stats=stats, pool=None)
                    dec.append(src)
                r_valid = seed_np[: seed.n]
                cand = [(s, s.candidate_block_ids(r_valid))
                        for _, _, s in keep]
                k_pad = max(s.self_pads()[0] for s, _ in cand)
                t_pad = max(s.self_pads()[1] for s, _ in cand)
                c_pad = max(its.pow2_bucket(len(b), floor=source.CAND_FLOOR)
                            for _, b in cand)
                e_max = max(s.num_exceptions for s, _ in cand)
                e_pad = its.pow2_bucket(e_max, floor=1) if e_max else 0
                psig = (k_pad, t_pad, c_pad, e_pad, rows, mode)
                if pool is not None:
                    # keep the PackedSource itself: the arena assembler
                    # materializes its group-padded layout rows on demand
                    # (memoized host-side, one device matrix per operand);
                    # block ids stay raw — the stacker pads them to the
                    # launching key's buckets (fusion may raise them)
                    psrc = [(s, b) for s, b in cand]
                else:
                    # memoized at the payload's own pads; the stacker
                    # zero-extends into the group slot (no per-group re-pad)
                    psrc = [(source.cached_layout_np(s, s.self_pads(), stats),
                             b) for s, b in cand]
                # decoded_ints for packed folds is accounted at LAUNCH
                # time (the program decodes c_pad blocks per row, and
                # fusion may raise c_pad past this group's bucket)
                source._bump(stats, "skip_folds", len(psrc))
            N = max((s.vals.shape[0] for s in dec), default=128)
            if pool is not None:
                r_op = seed.vals
                folds = dec                          # padded at stack time
            else:
                r_op = seed_np
                folds = [_extend_np(s.vals_np if s.vals_np is not None
                                    else np.asarray(s.vals), N) for s in dec]
            algo = ("tiled" if N / M <= BATCH_TILED_MAX_RATIO else "gallop")
            key = GroupKey("svs", M, N, W, algo, psig)
            groups[key].append(_Item(qi, pi, part.doc_lo, r=r_op,
                                     rsrc=seed if pool is not None else None,
                                     folds=folds, psrc=psrc,
                                     bm_words=bm_words, bm_dev=bm_dev,
                                     bm_keys=bm_keys))
    return groups


# --------------------------------------------------------------------------
# device programs (one dispatch per GroupKey chunk)
# --------------------------------------------------------------------------

def _mask_fold_scan(r, valid, folds, fold_active, intersect_fn):
    """Scan the stacked folds, ANDing each step's match mask into ``valid``.
    Every intersect runs against the *original* sorted seed buffer ``r``:
    compacting between folds never shrank the (static) operand shapes, but
    its cumsum+scatter was the single most expensive op in the program —
    mask-folding removes it, which is what keeps fused family-ceiling
    shapes affordable (DESIGN.md §2.10).  ``folds`` may be a plain
    (J, B, N) stack or any pytree of (J, ...)-leading operands (the packed
    layout tuple); inactive (j, b) slots leave their row's mask untouched."""
    def step(v, xs):
        f, act = xs
        hit = intersect_fn(r, f)
        return v & jnp.where(act[:, None], hit, True), None

    valid, _ = lax.scan(step, valid, (folds, fold_active))
    return valid


def _row_split(fn, mesh, *fold_args):
    """Run a megakernel per device of ``mesh`` over its slice of the batch
    rows.  XLA cannot partition a Mosaic kernel, and group programs are
    row-independent, so each device runs the kernel on the rows it holds:
    ``r``/``valid`` split on axis 0, fold-stacked operands on axis 1."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P
    row, fold = P("data"), P(None, "data")
    return jax.shard_map(
        fn, mesh=mesh, out_specs=row, check_vma=False,
        in_specs=(row, row) + tuple(
            jax.tree.map(lambda _: fold, a) for a in fold_args))


@partial(jax.jit, static_argnames=("algo", "backend", "mode", "block_rows",
                                   "mesh"),
         donate_argnums=_DONATE_CANDIDATES)
def _svs_program(r, folds, fold_active, pk, pk_active, words, algo: str,
                 backend: str, mode: str, block_rows: int, mesh=None):
    """One device program per group chunk: decoded folds → packed folds →
    bitmap probes, candidates staying on device throughout.  Every stage
    computes a match mask over the original sorted seed buffer ``r`` and
    ANDs it into one running validity mask; the result is ``r`` with
    invalid slots set to SENTINEL — per-row sorted but NOT compacted (the
    host extracts the valid prefix-by-mask at collect).  ``pk`` is the
    tuple of stacked batch-uniform packed operands (or None); ``words`` the
    stacked bitmap rows (or None).  ``r`` is donated (see module
    docstring).  ``mesh`` is the sharded executor's ('data',) mesh when
    the batch rows are split across devices (None on one device): the
    pallas megakernels then run per device on its rows."""
    valid = r != its.SENTINEL
    if folds.shape[0]:
        if backend == "pallas":
            # fused megakernel: the whole J-fold stack in one launch
            # (grid (B, J), mask accumulated in the revisited out block)
            from repro.kernels import ops as kernel_ops
            valid = _row_split(kernel_ops.intersect_fold_batch, mesh,
                               folds, fold_active)(r, valid, folds,
                                                   fold_active)
        else:
            if algo == "tiled":
                fold_fn = partial(its.intersect_tiled_batch,
                                  tile_r=min(128, r.shape[-1]),
                                  tile_f=min(1024, folds.shape[-1]))
            else:
                fold_fn = its.intersect_gallop_batch
            valid = _mask_fold_scan(r, valid, folds, fold_active, fold_fn)
    if pk is not None:
        if backend == "pallas":
            # fused decode+intersect megakernel: unpack candidate blocks in
            # kernel scratch, gallop, fold — one launch for the Jp stack,
            # no materialized decoded array (DESIGN.md §2.12)
            from repro.kernels import ops as kernel_ops
            valid = _row_split(
                partial(kernel_ops.intersect_packed_fold, mode=mode,
                        block_rows=block_rows),
                mesh, pk, pk_active)(r, valid, pk, pk_active)
        else:
            valid = _mask_fold_scan(
                r, valid, pk, pk_active,
                lambda rr, op: its.intersect_packed_batch(
                    rr, *op, mode=mode, block_rows=block_rows))
    if words is not None:
        def wstep(v, w):
            return jax.vmap(bm.probe)(w, r, v), None

        valid, _ = lax.scan(wstep, valid, words)
    return (jnp.where(valid, r, its.SENTINEL),
            jnp.sum(valid.astype(jnp.int32), axis=-1))


@jax.jit
def _bitmap_and_program(words):
    """All-bitmap queries: AND-reduce (B, J, W) word stacks + popcount."""
    out = words[:, 0]
    for j in range(1, words.shape[1]):
        out = out & words[:, j]
    counts = jnp.sum(lax.population_count(out).astype(jnp.int32), axis=-1)
    return out, counts


def _stack_packed(key: GroupKey, items: list[_Item], Bp: int,
                  jp: int | None = None):
    """Stack the per-item packed layouts into uniform (Jp, Bp, ...) numpy
    operands.  Layouts arrive self-padded (the memoized projection); each
    slot zero-extends into the key's buckets (which fusion may have raised
    past the scheduled group's) — pad blocks have width 0 and in-bounds
    offsets, and block ids beyond the real count never appear in the
    candidate list, so the extension is never decoded.  Raw candidate block
    ids pad with the key's out-of-range id ``k_pad`` (→ all-SENTINEL
    decode); inactive (j, b) slots keep all-pad block ids and are
    additionally masked by the active flags.  Returns (six host operand
    stacks, candidate block ids, active) — callers compose/upload."""
    k_pad, t_pad, c_pad, e_pad, rows, _ = key.packed
    Jp = (max((len(it.psrc) for it in items), default=0)
          if jp is None else jp)
    PW = np.zeros((Jp, Bp, t_pad, 128), np.uint32)
    PWid = np.zeros((Jp, Bp, k_pad), np.int32)
    POf = np.zeros((Jp, Bp, k_pad), np.int32)
    PMx = np.zeros((Jp, Bp, k_pad), np.uint32)
    PBk = np.full((Jp, Bp, c_pad), k_pad, np.int32)
    PEp = np.full((Jp, Bp, e_pad), -1, np.int32)
    PEa = np.zeros((Jp, Bp, e_pad), np.uint32)
    active = np.zeros((Jp, Bp), bool)
    for b, it in enumerate(items):
        for j, (lay, blk) in enumerate(it.psrc):
            K, T, E = (lay.widths.shape[0], lay.words.shape[0],
                       lay.exc_pos.shape[0])
            PW[j, b, :T] = lay.words
            PWid[j, b, :K] = lay.widths
            POf[j, b, :K] = lay.offsets
            PMx[j, b, :K] = lay.maxes
            PBk[j, b, : blk.shape[0]] = blk
            if e_pad and E:
                PEp[j, b, :E] = lay.exc_pos
                PEa[j, b, :E] = lay.exc_add
            active[j, b] = True
    return [PW, PWid, POf, PMx, PEp, PEa], PBk, active


# Arena gather: 2 arguments per assembled operand regardless of row count
# (the whole point of RowArena — see source.py); executes on the arena
# buffer's device, so per-shard slices stay on their shard's device.
_GATHER = jax.jit(lambda buf, idx: buf[idx])


def _stack_packed_arena(key: GroupKey, items: list[_Item], Bp: int,
                        pool: "source.ResidentPool",
                        jp: int | None = None):
    """Pool-mode packed stacking: gather each of the six layout operands
    from its RowArena with one (Jp·Bp,) index vector — slot 0 is the
    all-pad layout, so inactive grid positions decode to SENTINEL exactly
    like the host-stacked path.  Only the per-query candidate block ids
    cross to the device.  Returns (six device operand stacks, candidate
    block ids, active)."""
    k_pad, t_pad, c_pad, e_pad, rows, _ = key.packed
    pads = (k_pad, t_pad, e_pad)
    Jp = (max((len(it.psrc) for it in items), default=0)
          if jp is None else jp)
    arenas = [pool.layout_arena(pads, o) for o in range(6)]
    idx = np.zeros((Jp, Bp), np.int32)          # 0 = all-pad layout slot
    PBk = np.full((Jp, Bp, c_pad), k_pad, np.int32)
    active = np.zeros((Jp, Bp), bool)
    for b, it in enumerate(items):
        for j, (src, blk) in enumerate(it.psrc):
            slot = arenas[0].slots.get(src.key)
            if slot is None:
                lay = source.cached_layout_np(src, pads)
                ops = (lay.words, lay.widths, lay.offsets, lay.maxes,
                       lay.exc_pos, lay.exc_add)
                for a, row in zip(arenas, ops):
                    slot = a.slot(src.key, lambda r=row: np.asarray(r))
            idx[j, b] = slot
            PBk[j, b, : blk.shape[0]] = blk
            active[j, b] = True
    gidx = jnp.asarray(idx.reshape(-1))
    stacked = [_GATHER(a.buffer(), gidx).reshape(
                   (Jp, Bp) + a.rows_np[0].shape)
               for a in arenas]
    return stacked, PBk, active


def _compose_pk(stacked, PBk):
    """Order the packed program operand tuple from six stacked arrays +
    candidate block ids (device or host; the jit call uploads host parts)."""
    return (stacked[0], stacked[1], stacked[2], stacked[3],
            jnp.asarray(PBk), stacked[4], stacked[5])


def _n_bitmaps(it: _Item) -> int:
    return (it.bm_words.shape[0] if it.bm_words is not None
            else len(it.bm_dev) if it.bm_dev is not None else 0)


def _arena_ok(items: list[_Item]) -> bool:
    """Arena assembly needs a host copy + identity key for every value row;
    cache-hit sources carry neither (their numpy copy was dropped at cache
    fill), so groups containing them fall back to the row-stack path."""
    for it in items:
        if it.rsrc is None or it.rsrc.vals_np is None or not it.rsrc.key:
            return False
        for f in it.folds:
            if f.vals_np is None or not f.key:
                return False
    return True


def _assemble_svs(key: GroupKey, items: list[_Item],
                  pool: "source.ResidentPool | None", *,
                  bp: int | None = None, j: int | None = None,
                  jb: int | None = None, jp: int | None = None):
    """Build the operands of one svs group chunk.  Host mode stacks numpy
    and pays one H2D per operand; pool mode gathers resident rows (committed
    to the pool's device).  Rows narrower than the key's buckets (fused
    megagroup keys raise them past the scheduled shapes) extend with
    sentinel / zero-word filler — inert by the module's padding invariant.
    ``bp``/``j``/``jb``/``jp`` override the chunk-derived paddings so the
    sharded executor can assemble uniform per-shard slices
    (``repro.index.shard``); fused keys pin the arity ceilings via
    ``key.fused``; None derives them from the items — the single-device
    unfused path, unchanged."""
    B = len(items)
    kj, kjb, kjp = key.fused if key.fused else (None, None, None)
    Bp = _bucket_rows(B) if bp is None else bp
    if j is None:
        j = kj
    if jb is None:
        jb = kjb
    if jp is None:
        jp = kjp
    J = (max((len(it.folds) for it in items), default=0)
         if j is None else j)
    Jb = (max((_n_bitmaps(it) for it in items), default=0)
          if jb is None else jb)
    active = np.zeros((J, Bp), dtype=bool)
    if pool is not None and _arena_ok(items):
        # arena fast path: one gather per operand (DESIGN.md §2.8/§2.9)
        fa_m = pool.fold_arena(key.m_bucket)
        ridx = np.zeros(Bp, np.int32)               # 0 = sentinel row
        for b, it in enumerate(items):
            ridx[b] = fa_m.slot(
                it.rsrc.key,
                lambda s=it.rsrc: _extend_np(s.vals_np, key.m_bucket))
        R = _GATHER(fa_m.buffer(), jnp.asarray(ridx))
        if J:
            fa_n = pool.fold_arena(key.n_bucket)
            fidx = np.zeros((J, Bp), np.int32)
            for b, it in enumerate(items):
                for jj, f in enumerate(it.folds):
                    fidx[jj, b] = fa_n.slot(
                        f.key,
                        lambda s=f: _extend_np(s.vals_np, key.n_bucket))
                    active[jj, b] = True
            F = _GATHER(fa_n.buffer(),
                        jnp.asarray(fidx.reshape(-1))
                        ).reshape(J, Bp, key.n_bucket)
        else:
            F = jnp.zeros((0, Bp, key.n_bucket), jnp.int32)
        W = None
        if Jb:
            wa = pool.bitmap_arena(key.words)
            widx = np.zeros((Jb, Bp), np.int32)     # 0 = probe identity
            for b, it in enumerate(items):
                for jj, (bk, wnp) in enumerate(it.bm_keys or ()):
                    widx[jj, b] = wa.slot(
                        bk, lambda w=wnp: _extend_words(w, key.words))
            W = _GATHER(wa.buffer(),
                        jnp.asarray(widx.reshape(-1))
                        ).reshape(Jb, Bp, key.words)
    elif pool is not None:
        R = _stack_rows([pool.padded(it.rsrc, key.m_bucket) for it in items]
                        + [pool.sentinel_row(key.m_bucket)] * (Bp - B))
        rows = []
        for j in range(J):
            for b in range(Bp):
                it = items[b] if b < B else None
                if it is not None and j < len(it.folds):
                    rows.append(pool.padded(it.folds[j], key.n_bucket))
                    active[j, b] = True
                else:
                    rows.append(pool.sentinel_row(key.n_bucket))
        F = (_stack_rows(rows).reshape(J, Bp, key.n_bucket) if J
             else jnp.zeros((0, Bp, key.n_bucket), jnp.int32))
        W = None
        if Jb:
            wrows = []
            for j in range(Jb):
                for b in range(Bp):
                    it = items[b] if b < B else None
                    if it is not None and it.bm_dev and j < len(it.bm_dev):
                        wrows.append(_extend_words_dev(it.bm_dev[j],
                                                       key.words))
                    else:
                        # inactive slots are all-ones — the probe identity
                        wrows.append(pool.ones_row(key.words))
            W = _stack_rows(wrows).reshape(Jb, Bp, key.words)
    else:
        Rnp = np.full((Bp, key.m_bucket), its.SENTINEL, dtype=np.int32)
        for b, it in enumerate(items):
            Rnp[b, : it.r.shape[0]] = it.r
        R = jnp.asarray(Rnp)                                    # (Bp, M)
        F = np.full((J, Bp, key.n_bucket), its.SENTINEL, dtype=np.int32)
        for b, it in enumerate(items):
            for j, fold in enumerate(it.folds):
                F[j, b, : fold.shape[0]] = fold
                active[j, b] = True
        F = jnp.asarray(F)                                      # (J, Bp, N)
        W = None
        if Jb:
            # inactive slots are all-ones rows — the probe identity; the
            # zero extension past a real row's own W is never probed
            Wnp = np.full((Jb, Bp, key.words), 0xFFFFFFFF, dtype=np.uint32)
            for b, it in enumerate(items):
                if it.bm_words is not None:
                    for j in range(it.bm_words.shape[0]):
                        Wnp[j, b, : it.bm_words.shape[1]] = it.bm_words[j]
                        Wnp[j, b, it.bm_words.shape[1]:] = 0
            W = jnp.asarray(Wnp)
    pkparts = None
    if key.packed is not None:
        if pool is not None:
            pkparts = _stack_packed_arena(key, items, Bp, pool, jp=jp)
        else:
            pkparts = _stack_packed(key, items, Bp, jp=jp)
    return R, F, active, pkparts, W, Bp, J, Jb


def pallas_occupancy(key: GroupKey, items: list[_Item],
                     bp: int | None = None) -> float:
    """Fraction of the padded kernel grid that carries real work: (seed
    rows + decoded folds + packed folds) over Bp·(1 + J + Jp) family-
    ceiling slots.  This is exactly the ratio of useful to total grid
    steps the fused megakernels execute for the chunk.  ``bp`` overrides
    the batch bucket (the sharded launcher's grid is S·Bq rows)."""
    B = len(items)
    Bp = _bucket_rows(B) if bp is None else bp
    if key.fused:
        J, _, Jp = key.fused
        Jp = Jp or 0
    else:
        J = max((len(it.folds or ()) for it in items), default=0)
        Jp = max((len(it.psrc or ()) for it in items), default=0)
    real = (B + sum(len(it.folds or ()) for it in items)
            + sum(len(it.psrc or ()) for it in items))
    return real / max(Bp * (1 + J + Jp), 1)


def _effective_backend(key: GroupKey, items: list[_Item], backend: str,
                       stats: dict | None = None,
                       bp: int | None = None) -> str:
    """Occupancy guard (see PALLAS_MIN_OCCUPANCY above): demote a sparsely
    occupied chunk from interpret-mode pallas to the jax program.  Results
    are identical either way; only the execution engine changes."""
    if backend != "pallas":
        return backend
    from repro.kernels import ops as kernel_ops
    if kernel_ops.kernel_mode() == "compiled":
        return backend
    if pallas_occupancy(key, items, bp) < PALLAS_MIN_OCCUPANCY:
        source._bump(stats, "pallas_lowocc_fallbacks")
        return "jax"
    return backend


def count_folds(stats: dict | None, items: list, backend: str, r, folds,
                pk, block_rows: int):
    """Count the real folds one svs launch runs, and where: ``folds`` all
    of them; under the pallas backend ``kernel_folds`` those a megakernel
    runs and ``kernel_vmem_fallbacks`` those whose operands overflow the
    kernels' VMEM limit and so run the jnp reference.  ``r``/``folds``/
    ``pk`` are the launch's own operands, routed by the same
    ``kernels.ops`` functions the program branches on."""
    if stats is None:
        return
    items = [it for it in items if it is not None]
    dec = sum(len(it.folds or ()) for it in items)
    pk_n = sum(len(it.psrc or ()) for it in items)
    source._bump(stats, "folds", dec + pk_n)
    if backend != "pallas":
        return
    from repro.kernels import ops as kernel_ops
    kernel = dec if dec and kernel_ops.decoded_in_kernel(r, folds) else 0
    if pk_n and kernel_ops.packed_in_kernel(r, pk, block_rows):
        kernel += pk_n
    source._bump(stats, "kernel_folds", kernel)
    source._bump(stats, "kernel_vmem_fallbacks", dec + pk_n - kernel)


def _launch_svs_group(key: GroupKey, items: list[_Item], backend: str,
                      pool, stats: dict | None, timings=None):
    """Dispatch one svs device program; returns un-materialized device
    results (vals, counts).  The batch dimension is bucketed (sentinel-
    padded rows, results masked back at collect time) so the compile count
    stays bounded by the signature space.  ``timings`` (a
    ``pipeline.StageTimings``) splits operand assembly from the async
    program enqueue."""
    backend = _effective_backend(key, items, backend, stats)
    t0 = time.perf_counter()
    R, F, active, pkparts, W, Bp, J, Jb = _assemble_svs(key, items, pool)
    pk = pk_active = None
    if pkparts is not None:
        stacked, PBk, pk_act = pkparts
        pk = _compose_pk(stacked, PBk)
        pk_active = jnp.asarray(pk_act)
    mode, rows = "d1", 32
    if key.packed is not None:
        rows, mode = key.packed[4], key.packed[5]
        # actual partial-decode volume: every active packed slot decodes
        # c_pad blocks at the LAUNCHING key's bucket (fused keys raise it
        # past the scheduled group's, and the stat must track the work the
        # program really does)
        source._bump(stats, "decoded_ints",
                     sum(len(it.psrc) for it in items)
                     * key.packed[2] * rows * 128)
    count_folds(stats, items, backend, R, F, pk, rows)
    if stats is not None:
        stats.setdefault("signatures", set()).add(("svs", key, Bp, J, Jb))
    t1 = time.perf_counter()
    out = _svs_program(R, F, jnp.asarray(active), pk, pk_active, W,
                       key.algo, backend, mode, rows)
    if timings is not None:
        t2 = time.perf_counter()
        timings.assemble += t1 - t0
        timings.dispatch += t2 - t1
    return out


def _assemble_bitmap(key: GroupKey, items: list[_Item], pool, *,
                     bp: int | None = None, j: int | None = None):
    """Stacked (Bp, J, W) word rows of one all-bitmap group chunk (device
    array in pool mode, host numpy otherwise).  ``bp``/``j`` override the
    chunk-derived paddings for sharded per-shard slices; fused keys pin
    ``j`` via ``key.fused``.  Rows narrower than a fused W bucket
    zero-extend — every real row ANDs at least one zero extension, so the
    extension's popcount is 0."""
    B = len(items)
    Bp = _bucket_rows(B) if bp is None else bp
    if j is None and key.fused:
        j = key.fused[0]
    J = (max((_n_bitmaps(it) for it in items), default=1)
         if j is None else j)
    if pool is not None and all(it.bm_keys is not None for it in items):
        # arena fast path: missing terms of real rows gather the all-ones
        # AND identity (slot 0); padded batch rows gather all-zero (slot 1)
        wa = pool.bitmap_arena(key.words)
        widx = np.zeros((Bp, J), np.int32)
        widx[B:, :] = source.ResidentPool.BM_ZERO_SLOT
        for b, it in enumerate(items):
            for jj, (bk, wnp) in enumerate(it.bm_keys):
                widx[b, jj] = wa.slot(
                    bk, lambda w=wnp: _extend_words(w, key.words))
        words = _GATHER(wa.buffer(),
                        jnp.asarray(widx.reshape(-1))
                        ).reshape(Bp, J, key.words)
    elif pool is not None:
        rows = []
        for b in range(Bp):
            it = items[b] if b < B else None
            for j in range(J):
                if it is not None and j < len(it.bm_dev):
                    rows.append(_extend_words_dev(it.bm_dev[j], key.words))
                elif it is not None:
                    rows.append(pool.ones_row(key.words))   # AND identity
                else:
                    rows.append(pool.zeros_row(key.words))  # popcount 0
        words = _stack_rows(rows).reshape(Bp, J, key.words)
    else:
        # real rows pad missing terms with all-ones (AND identity); padded
        # batch rows — and every real row's words past its own W — stay
        # all-zero so their popcount contribution is 0
        wnp = np.zeros((Bp, J, key.words), dtype=np.uint32)
        for b, it in enumerate(items):
            wr = it.bm_words.shape[1]
            wnp[b, :, :wr] = 0xFFFFFFFF
            wnp[b, : it.bm_words.shape[0], :wr] = it.bm_words
        words = jnp.asarray(wnp)
    return words, Bp, J


def _launch_bitmap_group(key: GroupKey, items: list[_Item], pool,
                         stats: dict | None, timings=None):
    t0 = time.perf_counter()
    words, Bp, J = _assemble_bitmap(key, items, pool)
    if stats is not None:
        stats.setdefault("signatures", set()).add(("bm", key, Bp, J))
    t1 = time.perf_counter()
    out = _bitmap_and_program(words)
    if timings is not None:
        t2 = time.perf_counter()
        timings.assemble += t1 - t0
        timings.dispatch += t2 - t1
    return out


def _chunk_size(key: GroupKey, items: list[_Item],
                max_group_size: int) -> int:
    """Items per device program: flat cap ∧ operand-int budget (so huge
    J·N fold stacks shrink the batch instead of exploding device memory).
    Fused keys budget at their pinned arity ceilings."""
    if key.kind == "bitmap":
        J = (key.fused[0] if key.fused else
             max((it.bm_words.shape[0] if it.bm_words is not None
                  else len(it.bm_dev)) for it in items))
        per_item = J * key.words
    else:
        if key.fused:
            J, Jb, Jp = key.fused
        else:
            J = max(len(it.folds) for it in items)
            Jb = max((it.bm_words.shape[0] if it.bm_words is not None
                      else len(it.bm_dev) if it.bm_dev is not None else 0)
                     for it in items)
        per_item = J * key.n_bucket + key.m_bucket + Jb * key.words
        if key.packed is not None:
            k_pad, t_pad, c_pad, e_pad, rows, _ = key.packed
            if not key.fused:
                Jp = max(len(it.psrc) for it in items)
            # compressed words + per-block metadata + the partial decode
            # buffer the program materializes (c_pad blocks of rows×128)
            per_item += Jp * (t_pad * 128 + 3 * k_pad + c_pad
                              + 2 * e_pad + c_pad * rows * 128)
    return max(1, min(max_group_size, GROUP_INT_BUDGET // max(per_item, 1)))


# --------------------------------------------------------------------------
# megagroup fusion: collapse per-batch dispatch count (DESIGN.md §2.10)
# --------------------------------------------------------------------------

def _pow2_ceil(x: int) -> int:
    """Next power of two ≥ x (0 stays 0).  Fused arity ceilings are
    bucketed so the fused signature does not drift with each batch's exact
    arity mix."""
    return its.pow2_bucket(x, floor=1) if x > 0 else 0


class FusionPlan:
    """Sticky fused-dimension ceilings, one entry per signature family.

    Fused operand shapes are maxima over a batch's member groups; left
    alone they would drift batch to batch (a batch that happens to lack
    the longest list would compile a second, slightly smaller program).
    The plan makes ceilings *monotone*: every batch raises its family's
    sticky dims to at least everything previously seen, so fused
    signatures converge to a fixed point within the first few batches —
    which is what lets ``warmup`` reach that fixed point before serving
    starts.  Create one plan per serving session and pass it to every
    execute call (a fresh plan per call still fuses, it just re-derives
    ceilings per batch)."""

    def __init__(self):
        self.dims: dict[tuple, list[int]] = {}

    def raised(self, famid: tuple, dims: tuple) -> tuple:
        cur = self.dims.get(famid)
        if cur is None:
            self.dims[famid] = cur = list(dims)
        else:
            for i, d in enumerate(dims):
                if d > cur[i]:
                    cur[i] = d
        return tuple(cur)

    def covers(self, famid: tuple, dims: tuple) -> bool:
        """Read-only peek: would ``raised(famid, dims)`` change anything?
        True iff the family is known and every dim is within its sticky
        ceiling — i.e. fusing a batch with these family dims launches only
        already-established fused signatures."""
        cur = self.dims.get(famid)
        return cur is not None and all(d <= c for d, c in zip(dims, cur))


def _families(groups: dict[GroupKey, list[_Item]]) -> dict[tuple, list]:
    """Bucket scheduled groups into signature families — (kind, packed
    block geometry) — the identity ``fuse_groups`` coarsens within."""
    fams: dict[tuple, list] = {}
    for key, items in groups.items():
        geom = None if key.packed is None else (key.packed[4], key.packed[5])
        fams.setdefault((key.kind, geom), []).append((key, items))
    return fams


def _family_dims(kind: str, geom, members: list) -> tuple:
    """Ceiling dims of one family over its member (key, items) pairs — the
    shared derivation ``fuse_groups`` raises through the sticky plan and
    ``plan_covers`` peeks at.  Layout: bitmap -> (W, Jb); svs ->
    (M, N, W, J, Jb[, k, t, c, e, Jp])."""
    items = [it for _, mi in members for it in mi]
    if kind == "bitmap":
        return (max(k.words for k, _ in members),
                _pow2_ceil(max(_n_bitmaps(it) for it in items)))
    dims = [max(k.m_bucket for k, _ in members),
            max(k.n_bucket for k, _ in members),
            max(k.words for k, _ in members),
            _pow2_ceil(max(len(it.folds) for it in items)),
            _pow2_ceil(max(_n_bitmaps(it) for it in items))]
    if geom is not None:
        dims += [max(k.packed[i] for k, _ in members) for i in range(4)]
        dims.append(_pow2_ceil(max(len(it.psrc) for it in items)))
    return tuple(dims)


def plan_covers(groups: dict[GroupKey, list[_Item]],
                plan: FusionPlan | None) -> bool:
    """Family-signature admission predicate (DESIGN.md §2.11): True iff
    fusing ``groups`` under ``plan`` would not raise any sticky family
    ceiling — i.e. the batch launches only fused signatures the plan has
    already established (after ``warmup``, ones that are already
    compiled).  The continuous-batching server uses this to account for
    admission decisions that would stall a latency-bound batch on a
    compile; it never changes the plan (read-only peek, evaluate BEFORE
    ``fuse_groups`` makes the ceilings monotone over this batch)."""
    if plan is None:
        return False
    return all(plan.covers((kind, geom), _family_dims(kind, geom, members))
               for (kind, geom), members in _families(groups).items())


def fuse_groups(groups: dict[GroupKey, list[_Item]],
                plan: FusionPlan | None = None,
                stats: dict | None = None) -> dict[GroupKey, list[_Item]]:
    """Coarsen scheduled GroupKeys into signature *families* and merge each
    family's items along the batch-row axis, so a mixed batch launches
    O(#families) fused device programs instead of one per signature.

    A family is (kind, packed block geometry).  Every shape dimension that
    is NOT part of the family identity — the M/N/W buckets, the packed
    k/t/c/e pads, and the pow2-bucketed fold/probe arities — is raised to
    the family ceiling (max over member groups, further raised by the
    sticky ``plan``).  This is sound because group programs are
    row-independent and padding is inert (module invariants): a row
    assembled into a wider slot meets sentinel filler, masked no-op folds,
    all-pad packed layouts, and identity bitmap rows, none of which change
    its result.  ``tests/test_fusion.py`` pins fused == unfused ==
    sequential byte for byte across backends, corpora, and shard counts.

    Fused svs programs force ``algo='gallop'``: the tiled ratio rule was
    derived per scheduled group, family ceilings inflate M against it, and
    the vmapped tile walk loses its data-dependent early exit entirely at
    ceiling shapes, while galloping stays O(M log N) per row regardless of
    padding.  Groups without packed folds keep their own (svs, None)
    family rather than joining a packed one — inactive packed slots would
    still pay the partial decode for every row.

    The candidate-block bucket ``c_pad`` is the one ceiling that costs
    real decode work (each row partially decodes c_pad blocks whether it
    needs them or not), so it is batch-derived and only the plan's
    stickiness widens it: fused decode volume is bounded by the observed
    workload, never by the index size.
    """
    fused: dict[GroupKey, list[_Item]] = {}
    for (kind, geom), members in _families(groups).items():
        items = [it for _, mi in members for it in mi]
        dims = _family_dims(kind, geom, members)
        if plan is not None:
            dims = plan.raised((kind, geom), dims)
        if kind == "bitmap":
            w, jb = dims
            fkey = GroupKey("bitmap", 0, 0, w, "-", fused=(jb,))
        else:
            m, n, w, j, jb = dims[:5]
            packed = (tuple(dims[5:9]) + geom) if geom is not None else None
            jp = dims[9] if geom is not None else 0
            fkey = GroupKey("svs", m, n, w, "gallop", packed,
                            fused=(j, jb, jp))
        fused[fkey] = items
    if stats is not None:
        stats["n_sched_groups"] = (stats.get("n_sched_groups", 0)
                                   + len(groups))
        stats["n_fused_groups"] = (stats.get("n_fused_groups", 0)
                                   + len(fused))
    return fused


def _compile_count() -> int:
    """Total jit-cache entries behind the group programs, the arena
    gather, and every row stacker (the arena-fallback path compiles stack
    programs mid-serving, e.g. when a cache fill drops a row's host copy)
    — the compiles ``warmup`` is meant to front-load.  Reads jax's jit
    ``_cache_size``, which the installed jax provides; a jax without it
    fails here rather than reporting 0 compiles."""
    return sum(fn._cache_size()
               for fn in (_svs_program, _bitmap_and_program, _GATHER,
                          *_STACKERS))


# --------------------------------------------------------------------------
# launch / collect (the pipeline split) and the public entry point
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PendingBatch:
    """Dispatched-but-unmaterialized batch: device result handles per group
    chunk.  JAX async dispatch means the device is (or will be) executing
    these while the host moves on; ``collect_batch`` blocks on them."""
    n_queries: int
    max_results: int
    launched: list          # [(key, chunk_items, vals_dev, counts_dev)]
    stats: dict | None


def launch_groups(groups: dict[GroupKey, list[_Item]], *, n_queries: int,
                  backend: str = "jax", max_results: int = 1 << 16,
                  max_group_size: int = MAX_GROUP_SIZE,
                  pool: "source.ResidentPool | None" = None,
                  stats: dict | None = None, timings=None) -> PendingBatch:
    """Dispatch one device program per (possibly fused) group chunk without
    materializing any result — the host returns as soon as everything is
    enqueued.  ``timings`` (a ``pipeline.StageTimings``) attributes operand
    assembly vs program enqueue wall time."""
    launched = []
    n_dispatches = 0
    c0 = _compile_count() if stats is not None else 0
    for key, items in groups.items():
        step = _chunk_size(key, items, max_group_size)
        for lo in range(0, len(items), step):
            chunk = items[lo: lo + step]
            if key.kind == "bitmap":
                vals, counts = _launch_bitmap_group(key, chunk, pool, stats,
                                                    timings)
            else:
                vals, counts = _launch_svs_group(key, chunk, backend, pool,
                                                 stats, timings)
            launched.append((key, chunk, vals, counts))
            n_dispatches += 1
    accumulate_launch_stats(stats, groups, n_dispatches)
    if stats is not None:
        stats["n_compiles"] = (stats.get("n_compiles", 0)
                               + _compile_count() - c0)
    return PendingBatch(n_queries=n_queries, max_results=max_results,
                        launched=launched, stats=stats)


def accumulate_launch_stats(stats: dict | None, groups, n_dispatches: int):
    """Accumulate per-launch counters (like the decoded_ints/skip_folds
    counters) so one stats dict can span a chunked run of many batches —
    shared by the single-device and sharded launchers.  ``n_programs``
    stays an alias of ``n_dispatches`` (the historical name; both count
    device program launches — distinct *compiled* programs are
    ``len(stats['signatures'])``)."""
    if stats is None:
        return
    for k, v in (("n_groups", len(groups)), ("n_dispatches", n_dispatches),
                 ("n_programs", n_dispatches),
                 ("n_items", sum(len(v) for v in groups.values()))):
        stats[k] = stats.get(k, 0) + v


def collect_batch(pending: PendingBatch) -> list[QueryResult]:
    """Materialize a launched batch (blocks on the device) and re-assemble
    per-query results in part order — byte-identical to ``engine.query``.
    svs rows arrive masked-but-uncompacted (valid entries are the
    non-sentinel slots, still sorted); extraction happens here on host."""
    per_query: list[list[tuple[int, np.ndarray]]] = \
        [[] for _ in range(pending.n_queries)]
    counts = [0] * pending.n_queries
    for key, chunk, vals_dev, counts_dev in pending.launched:
        vals = np.asarray(vals_dev)
        cnts = np.asarray(counts_dev)
        for b, it in enumerate(chunk):
            if it is None:          # padded slot (sharded shard-slice pad)
                continue
            cnt = int(cnts[b])
            counts[it.qi] += cnt
            if not cnt:
                continue
            if key.kind == "bitmap":
                docs = bm.extract_np(vals[b])
            else:
                row = vals[b]
                docs = row[row != its.SENTINEL]
            per_query[it.qi].append((it.pi, docs.astype(np.int64)
                                     + it.doc_lo))
    out = []
    for qi in range(pending.n_queries):
        chunks = [d for _, d in sorted(per_query[qi], key=lambda x: x[0])]
        docs = (np.concatenate(chunks) if chunks
                else np.zeros(0, np.int64))[: pending.max_results]
        out.append(QueryResult(count=counts[qi], docs=docs))
    return out


def execute_batch(index: HybridIndex, queries: list[list[int]], *,
                  backend: str = "jax", max_results: int = 1 << 16,
                  max_group_size: int = MAX_GROUP_SIZE, cache=None,
                  skip: bool = True, stats: dict | None = None,
                  pool: "source.ResidentPool | None" = None,
                  fuse: bool = True, plan: FusionPlan | None = None
                  ) -> list[QueryResult]:
    """Answer a batch of conjunctive queries; results are element-for-element
    identical to ``engine.query`` run per query.

    backend: 'jax' (searchsorted/tile-merge) or 'pallas' (galloping kernel).
    skip: False forces full decode of every fold list (the pre-skip
    behavior, kept for A/B benchmarking of the partial-decode win).
    pool: optional ResidentPool — operands are served from (and staged
    into) the device-resident index; group assembly becomes index-gathering
    over resident buffers instead of per-batch decode + padding + H2D.
    fuse: coarsen the scheduled groups into megagroup families so the
    batch launches O(#families) device programs (DESIGN.md §2.10); False
    keeps one program per scheduled signature (the pre-fusion behavior,
    kept for A/B benchmarking — results are byte-identical either way).
    plan: optional FusionPlan carrying sticky family ceilings across calls
    (pass one per serving session so fused signatures converge; None
    re-derives ceilings per batch).
    stats: optional dict, filled with scheduler counters (n_groups,
    n_sched_groups/n_fused_groups, n_dispatches, n_compiles, n_items,
    decoded_ints, skip_folds, resident_hits, layout_hits/misses) for
    introspection.
    """
    assert backend in ("jax", "pallas"), backend
    groups = schedule(index, queries, cache=cache, skip=skip, stats=stats,
                      pool=pool)
    if fuse:
        groups = fuse_groups(groups, plan=plan, stats=stats)
    pending = launch_groups(groups, n_queries=len(queries), backend=backend,
                            max_results=max_results,
                            max_group_size=max_group_size, pool=pool,
                            stats=stats)
    return collect_batch(pending)


# --------------------------------------------------------------------------
# AOT signature warmup (DESIGN.md §2.10)
# --------------------------------------------------------------------------

def synth_warmup_queries(index: HybridIndex, n: int, seed: int = 0,
                         arities=(2, 3, 4, 5)) -> list[list[int]]:
    """Synthesize a warmup query sample from the index's own term stats —
    the fallback when no representative slice of the real stream is at
    hand.  Seeds draw from the shortest tercile of list terms (the seed of
    a real conjunctive query is its *shortest* list, so sampling seeds
    uniformly would sticky the plan's M ceiling to the longest list and
    permanently oversize every fused program); the remaining positions
    draw uniformly so fold/bitmap/packed families all get exercised."""
    rng = np.random.default_rng(seed)
    lens: dict[int, int] = {}
    for part in index.parts:            # aggregate over ALL parts: a term
        for tid, tp in part.terms.items():   # may be empty in part 0 only
            if tp.kind != "empty":
                lens[tid] = lens.get(tid, 0) + tp.n
    terms = sorted(lens.items(), key=lambda t: t[1])
    if not terms:
        return []
    ids = [t for t, _ in terms]
    short = ids[: max(len(ids) // 3, 1)]
    queries = []
    for i in range(n):
        a = arities[i % len(arities)]
        q = {int(rng.choice(short))}
        while len(q) < min(a, len(ids)):
            q.add(int(rng.choice(ids)))
        queries.append(sorted(q))
    return queries


def warm_to_fixed_point(run_fn, max_passes: int = 4
                        ) -> tuple[int, int, bool]:
    """Repeat ``run_fn(stats)`` until a pass adds no new program signature
    (cache fills, pool staging, and sticky plan ceilings all change how
    batches compile between passes).  Returns (n_signatures, passes,
    converged) — the one convergence rule shared by ``warmup`` and
    serve.py's warm loops.  ``converged`` is False when the loop ran out
    of ``max_passes`` while the last pass was still adding signatures: a
    timed loop after a non-converged warm pays hidden compiles that
    ``n_compiles == 0`` assertions on *later* batches silently miss, so
    callers must surface it (serve.py / ``warmup`` warn)."""
    stats: dict = {}
    seen = -1
    passes = 0
    converged = False
    for _ in range(max_passes):
        run_fn(stats)
        passes += 1
        n_sigs = len(stats.get("signatures", ()))
        if n_sigs == seen:
            converged = True
            break
        seen = n_sigs
    return len(stats.get("signatures", ())), passes, converged


def warmup(index: HybridIndex, queries: list[list[int]] | None = None, *,
           plan: FusionPlan, batch_size: int = 32, backend: str = "jax",
           pool: "source.ResidentPool | None" = None, cache=None,
           skip: bool = True, max_group_size: int = MAX_GROUP_SIZE,
           max_passes: int = 4, seed: int = 0) -> dict:
    """AOT signature warmup: precompile the fused family ladder before the
    first real batch, so steady-state serving never compiles.

    Runs the fused pipeline over ``queries`` — a representative sample of
    the expected workload; pass a slice of the real stream when one is at
    hand, else ``synth_warmup_queries`` fabricates one from the index term
    stats — repeating until no new program signature appears.  Repetition
    matters twice over: pool staging and cache fills change how terms
    resolve between passes (decoded vs packed), and the sticky ``plan``
    ceilings only reach their fixed point once a pass stops raising them.
    Every compile this triggers is one the first serving batches would
    otherwise have stalled on (a realistic mixed batch used to pay the
    whole signature ladder; fused it pays O(#families) compiles, all of
    them front-loaded here).

    Returns ``{"n_compiles", "n_signatures", "passes", "converged",
    "time_s"}`` — the compile count is measured from jax's jit caches, and
    a steady-state serve loop after warmup should report ``n_compiles ==
    0``.  ``converged`` is False when the signature ladder was still
    growing at ``max_passes`` (see ``warm_to_fixed_point``) — the
    zero-compile steady-state claim does not hold then, and callers
    should warn."""
    t0 = time.perf_counter()
    c0 = _compile_count()
    if queries is None:
        queries = synth_warmup_queries(index, 2 * batch_size, seed=seed)

    def one_pass(stats):
        for lo in range(0, len(queries), batch_size):
            execute_batch(index, queries[lo: lo + batch_size],
                          backend=backend, cache=cache, skip=skip,
                          pool=pool, fuse=True, plan=plan,
                          max_group_size=max_group_size, stats=stats)

    n_signatures, passes, converged = warm_to_fixed_point(one_pass,
                                                          max_passes)
    return {"n_compiles": _compile_count() - c0,
            "n_signatures": n_signatures,
            "passes": passes,
            "converged": converged,
            "time_s": time.perf_counter() - t0}
