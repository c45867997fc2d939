"""Pallas TPU intersection probe: SIMD Galloping (paper §5, Algorithm 4).

The paper gallops a scalar over the long list ``f`` to the block that may
hold each element of the short list ``r``, then compares the element
against that whole block in one SIMD instruction.  Mosaic has no vector
gather, so the TPU form keeps the same two steps at tile granularity
(DESIGN.md §2.4):

* **gallop** — for each 128-lane tile of ``r`` (sorted), scalar reads of
  the block maxima of ``f`` (one block = one (8, 128) vreg tile of sorted
  values) gallop from the previous tile's block to the first block that
  can hold the tile's smallest live value: exponential steps, then a
  binary narrowing;
* **block comparison** — every block whose first value is ≤ the tile's
  largest live value is compared against all 128 ``r`` lanes at once: the
  tile is transposed into a (128, 128) column broadcast and each of the
  block's 8 rows is one equality test per vreg.  A lane is a hit when any
  compared ``f`` value equals it.

Work per tile is the blocks its value range overlaps, and the block
pointer only moves forward, so a whole row costs O(M/128 + N/1024) block
comparisons plus O(log) scalar probes per tile — merge-like for dense
pairs, galloping for skewed ones.  Lanes whose running validity is
already 0 do not widen the window, so later folds of an SvS chain get
cheaper as candidates die.

``probe_tiles`` is the in-kernel routine shared by both megakernels
(``megakernel.py``); ``gallop_tiles`` / ``gallop_tiles_batched`` /
``packed_gallop_batched`` are the per-fold entry points, each a one-fold
launch of the corresponding megakernel.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import bitpack as core_bitpack

LANES = core_bitpack.LANES
SENTINEL = np.int32(2**31 - 1)
BLOCK_ROWS = 8                     # one (8, 128) tile of f per comparison


def _block_first(f_ref, k):
    return f_ref[k * BLOCK_ROWS, 0]


def _block_last(f_ref, k):
    return f_ref[k * BLOCK_ROWS + BLOCK_ROWS - 1, LANES - 1]


def _first_block_reaching(f_ref, k, n_blk: int, x):
    """Gallop: the first block index ≥ ``k`` whose last value is ≥ ``x``
    (``n_blk`` when none is).  Every block before ``k`` must end below
    ``x``."""
    def grow(s):
        lo, step = s
        p = lo + step - 1
        return (p < n_blk) & (_block_last(f_ref, jnp.minimum(p, n_blk - 1))
                              < x)

    lo, step = lax.while_loop(grow, lambda s: (s[0] + s[1], s[1] * 2),
                              (k, jnp.int32(1)))

    def halve(s):
        lo, hi = s
        mid = (lo + hi) // 2
        big = _block_last(f_ref, mid) >= x
        return jnp.where(big, lo, mid + 1), jnp.where(big, mid, hi)

    lo, _ = lax.while_loop(lambda s: s[0] < s[1], halve,
                           (lo, jnp.minimum(lo + step - 1, n_blk)))
    return lo


def probe_tiles(r_ref, f_ref, mask_ref):
    """AND the membership of every ``r`` lane in ``f`` into ``mask_ref``.

    r_ref: (Tr, 128) int32 sorted ascending, SENTINEL-padded; f_ref:
    (Rf, 128) int32 sorted ascending, SENTINEL-padded, Rf % 8 == 0;
    mask_ref: (Tr, 128) int32 0/1 running validity, read for liveness and
    overwritten with ``mask & (r in f) & (r != SENTINEL)``."""
    n_blk = f_ref.shape[0] // BLOCK_ROWS

    def tile(t, k):
        r = r_ref[pl.ds(t, 1), :]                        # (1, 128)
        live = (mask_ref[pl.ds(t, 1), :] != 0) & (r != SENTINEL)
        lo_v = jnp.min(jnp.where(live, r, SENTINEL))
        hi_v = jnp.max(jnp.where(live, r, -1))

        def probe(k):
            k = _first_block_reaching(f_ref, k, n_blk, lo_v)
            rows = jnp.broadcast_to(r, (LANES, LANES)).T     # rows[i] = r[i]

            def more(s):
                kk, _ = s
                return (kk < n_blk) & (
                    _block_first(f_ref, jnp.minimum(kk, n_blk - 1)) <= hi_v)

            def compare(s):
                kk, acc = s
                blk = f_ref[pl.ds(pl.multiple_of(kk * BLOCK_ROWS,
                                                 BLOCK_ROWS), BLOCK_ROWS), :]
                for i in range(BLOCK_ROWS):
                    acc = acc | (rows == blk[i:i + 1, :]).astype(jnp.int32)
                return kk + 1, acc

            _, acc = lax.while_loop(
                more, compare, (k, jnp.zeros((LANES, LANES), jnp.int32)))
            return k, jnp.max(acc.T, axis=0, keepdims=True)

        k, hit = lax.cond(hi_v >= lo_v, probe,
                          lambda k: (k, jnp.zeros((1, LANES), jnp.int32)), k)
        mask_ref[pl.ds(t, 1), :] = jnp.where(live, hit, 0)
        return k

    lax.fori_loop(0, r_ref.shape[0], tile, jnp.int32(0))


# --------------------------------------------------------------------------
# per-fold entry points: one-fold launches of the megakernels
# --------------------------------------------------------------------------

def gallop_tiles_batched(r, f, interpret: bool = True):
    """Batched galloping: r (B, M) sentinel-padded, M % 128 == 0; f (B, N)
    sentinel-padded, sorted.  Returns the (B, M) bool match mask."""
    from repro.kernels import megakernel
    B = r.shape[0]
    return megakernel.decoded_fold_batched(
        r, r != SENTINEL, f[None], jnp.ones((1, B), jnp.int32),
        interpret=interpret)


def gallop_tiles(r, f, interpret: bool = True):
    """r (M,) sentinel-padded, M % 128 == 0; f (N,) sorted, sentinel-padded.
    Returns the (M,) bool match mask."""
    return gallop_tiles_batched(r[None], f[None], interpret=interpret)[0]


def packed_gallop_batched(r, words, widths, offsets, maxes, blk_ids,
                          exc_pos, exc_add, mode: str, block_rows: int,
                          interpret: bool = True):
    """Batched skip-aware packed gallop: decode each row's candidate blocks
    in kernel scratch and probe them.  r (B, M) sentinel-padded int32;
    words (B, Tp, 128) uint32; widths/offsets/maxes (B, Kp); blk_ids (B, C);
    exc_pos/exc_add (B, E) FastPFOR patches (-1-padded).  Returns the
    (B, M) bool match mask."""
    from repro.kernels import megakernel
    B = r.shape[0]
    return megakernel.packed_fold_batched(
        r, r != SENTINEL, words[None], widths[None], offsets[None],
        maxes[None], blk_ids[None], exc_pos[None], exc_add[None],
        jnp.ones((1, B), jnp.int32), mode=mode, block_rows=block_rows,
        interpret=interpret)
