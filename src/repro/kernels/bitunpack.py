"""Pallas TPU kernel: integrated bit-unpack + prefix sum (paper Algorithm 1).

One grid step decodes one block: a (32, 128) packed-word VMEM tile (only the
first ``b`` rows carry data) → a (32, 128) value tile.  The prefix sum is
computed *in the same pass* as the unpacking, row by row, exactly as the
paper's integrated variant: per output row ``t ← (y[w] ≫ sh) | (y[w+1] ≪
(32−sh)) & M;  t ← P(t, v); v ← t`` — where P is selected by the delta mode.
The two-pass ("-NI") comparison point materializes deltas first (see ops.py).

Working set per grid step: 32·128·4 B in + 32·128·4 B out = 32 KiB ≪ VMEM.
Bit widths and seeds ride in scalar-prefetch (SMEM), mirroring the paper's
per-block metadata bytes.  Packed rows are read straight from the input
ref at their dynamic word index (``ref[pl.ds(w, 1), :]``) and lane scans
are shift-adds over ``pltpu.roll`` — the forms Mosaic lowers.

``unpack_row`` / ``prefix_rows`` / ``decode_candidates`` are the in-kernel
stages the packed megakernel (``megakernel.py``) reuses to decode a row's
candidate blocks into VMEM scratch.

Validated against ``ref.unpack_blocks_ref`` (pure jnp) in interpret mode for
every bit width b ∈ [0, 32] × every delta mode (tests/test_kernels.py).
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import deltas as core_deltas

ROWS = 32
LANES = 128
SENTINEL = np.int32(2**31 - 1)


def _lane():
    return lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def lane_scan(x, s: int = 1):
    """Inclusive stride-``s`` scan over the 128 lanes of a (1, 128) int32
    row: lane l sums x[l], x[l-s], x[l-2s], … (Hillis–Steele shift-adds)."""
    lane = _lane()
    k = s
    while k < LANES:
        x = x + jnp.where(lane >= k, pltpu.roll(x, k, 1), 0)
        k *= 2
    return x


def _phase_tail(row, s: int):
    """Per-lane carry of a stride-``s`` chain: lane l gets the last value
    of its phase in ``row`` (row[128 − s + l mod s])."""
    if s == 1:
        return jnp.broadcast_to(row[:, LANES - 1:], (1, LANES))
    return lane_scan(jnp.where(_lane() < s, pltpu.roll(row, s, 1), 0), s)


def prefix_rows(ref, base, n_rows: int, seed, mode: str):
    """In-place prefix sum P of rows [base, base + n_rows) of an int32
    (·, 128) ref holding one block's deltas, seeded with the scalar
    ``seed`` (int32 arithmetic wraps like the uint32 reference)."""
    if mode == "none":
        return
    carry = jnp.full((1, LANES), seed, jnp.int32)
    for r in range(n_rows):
        d = ref[pl.ds(base + r, 1), :]
        if mode == "dv":
            row = d + carry
            carry = row
        elif mode == "dm":
            row = d + carry
            carry = _phase_tail(row, 1)
        else:
            s = core_deltas._STRIDE[mode]
            row = lane_scan(d, s) + carry
            carry = _phase_tail(row, s)
        ref[pl.ds(base + r, 1), :] = row


def unpack_row(w_ref, off, width, r: int):
    """Row ``r`` of the block of bit width ``width`` whose packed words
    start at row ``off`` of the (T, 128) uint32 ref — returned as int32."""
    t_max = w_ref.shape[0] - 1
    start = r * width
    w = start >> 5
    sh = (start & 31).astype(jnp.uint32)
    bu = width.astype(jnp.uint32)
    lo = w_ref[pl.ds(jnp.minimum(off + w, t_max), 1), :]
    hi = w_ref[pl.ds(jnp.minimum(off + w + 1, t_max), 1), :]
    mask = jnp.where(width >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << jnp.minimum(bu, 31)) - 1)
    t = lo >> sh
    t = jnp.where(sh + bu > 32, t | (hi << ((jnp.uint32(32) - sh) & 31)), t)
    return lax.bitcast_convert_type(t & mask, jnp.int32)


def scalar_at(ref, i):
    """Element ``i`` of a flat int32 vector laid out as a (·, 128) ref.
    Mosaic reads a scalar from VMEM only at a static lane, so the row is
    loaded and the lane selected by a masked reduction."""
    row = ref[pl.ds(i // LANES, 1), :]
    return jnp.sum(jnp.where(_lane() == i % LANES, row, 0))


def _patch_exceptions(ep_ref, ea_ref, win_ref, e, bid, base, per: int,
                      n_exc: int):
    """Add the FastPFOR patches of block ``bid`` to its deltas at window
    rows [base, base + per/128).  Exceptions are sorted by position (-1
    padded at the tail) and candidate blocks ascend, so the pointer ``e``
    only moves forward across a row's blocks."""
    lo_pos = bid * per

    def pos(e):
        return scalar_at(ep_ref, jnp.minimum(e, n_exc - 1))

    def ahead(e):
        p = pos(e)
        return (e < n_exc) & (p >= 0) & (p < lo_pos)

    e = lax.while_loop(ahead, lambda e: e + 1, e)

    def inside(e):
        p = pos(e)
        return (e < n_exc) & (p >= 0) & (p < lo_pos + per)

    def patch(e):
        p = pos(e) - lo_pos
        row = base + p // LANES
        add = scalar_at(ea_ref, e)
        cur = win_ref[pl.ds(row, 1), :]
        win_ref[pl.ds(row, 1), :] = cur + jnp.where(_lane() == p % LANES,
                                                    add, 0)
        return e + 1

    return lax.while_loop(inside, patch, e)


def decode_candidates(w_ref, wid_ref, off_ref, max_ref, blk_ref, ep_ref,
                      ea_ref, win_ref, *, mode: str, block_rows: int,
                      k_pad: int, n_cand: int, n_exc: int):
    """In-kernel partial decode of one row's candidate blocks into the
    (n_cand·block_rows, 128) int32 scratch ``win_ref`` — a sorted window,
    SENTINEL on pad slots.

    This is the scratch-decode stage of the packed megakernel: read each
    candidate block's width/offset, bit-unpack its deltas with the same
    shift/mask rows as the Algorithm-1 kernel above, patch FastPFOR
    exceptions whose block made the candidate list, and prefix-sum with
    the per-block seed (the previous block's max).  Metadata refs are flat
    int32 vectors laid out (·, 128).  Block ids ≥ ``k_pad`` are pad
    candidates: their rows are SENTINEL, so the window stays sorted and the
    probe can never match a pad slot (DESIGN.md §2.6, §2.12)."""
    per = block_rows * LANES

    def block(c, e):
        bid = scalar_at(blk_ref, c)
        base = pl.multiple_of(c * block_rows, 8)

        def real(e):
            width = scalar_at(wid_ref, bid)
            off = scalar_at(off_ref, bid)
            seed = jnp.where(bid > 0,
                             scalar_at(max_ref, jnp.maximum(bid - 1, 0)), 0)
            for r in range(block_rows):
                win_ref[pl.ds(base + r, 1), :] = unpack_row(w_ref, off,
                                                            width, r)
            if n_exc:
                e = _patch_exceptions(ep_ref, ea_ref, win_ref, e, bid, base,
                                      per, n_exc)
            prefix_rows(win_ref, base, block_rows, seed, mode)
            return e

        def pad(e):
            win_ref[pl.ds(base, block_rows), :] = jnp.full(
                (block_rows, LANES), SENTINEL, jnp.int32)
            return e

        return lax.cond(bid < k_pad, real, pad, e)

    lax.fori_loop(0, n_cand, block, jnp.int32(0))


def make_unpack_kernel(mode: str):
    """Build the Algorithm-1 kernel body for one delta mode P."""

    def kernel(widths_ref, seeds_ref, words_ref, out_ref):
        k = pl.program_id(0)
        width = widths_ref[k]
        for r in range(ROWS):                      # static unroll, as in the
            out_ref[pl.ds(r, 1), :] = unpack_row(  # paper's generated code
                words_ref, 0, width, r)
        prefix_rows(out_ref, 0, ROWS, seeds_ref[k], mode)

    return kernel


@partial(jax.jit, static_argnames=("mode", "interpret"))
def unpack_blocks(padded_words, widths, seeds, mode: str = "d1",
                  interpret: bool = True):
    """padded_words: (K, 32, 128) uint32 (block-padded packed words);
    widths, seeds: (K,).  Returns (K, 32, 128) uint32 decoded values."""
    from repro.kernels.megakernel import compiler_params

    K = padded_words.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # widths, seeds → SMEM
        grid=(K,),
        in_specs=[pl.BlockSpec((None, ROWS, LANES), lambda k, *_: (k, 0, 0))],
        out_specs=pl.BlockSpec((None, ROWS, LANES), lambda k, *_: (k, 0, 0)),
    )
    out = pl.pallas_call(
        make_unpack_kernel(mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, ROWS, LANES), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="unpack_blocks",
    )(widths.astype(jnp.int32),
      lax.bitcast_convert_type(seeds.astype(jnp.uint32), jnp.int32),
      padded_words.astype(jnp.uint32))
    return lax.bitcast_convert_type(out, jnp.uint32)
