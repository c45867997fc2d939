"""Stream VByte batched device decode: pure-jnp path + Pallas TPU kernel.

Both implement the same lane-parallel reconstruction (arXiv 1709.08990 /
DESIGN.md §2.13): control words → per-lane 2-bit codes → byte widths →
prefix-summed byte offsets → gather the two uint32 data words straddling
each value's byte offset → shift/mask out the 1–4 value bytes → delta
prefix sum with the per-block scalar seed.  This mirrors the candidate
decode of ``bitunpack.decode_candidates``: every shape is static, the body
is vectorized jnp, and pad blocks decode harmlessly (code 0 → width-1
lanes reading clamped in-bounds bytes) with callers trimming to the valid
count.

The Pallas kernel follows the ``bitunpack.unpack_blocks`` idiom: one grid
step per block, per-block data byte offset + seed in scalar prefetch
(SMEM), and the control and data streams resident in VMEM across steps
(constant BlockSpec index maps) since byte offsets cross block
boundaries.  Mosaic has no general vector gather, so each 128-value row
gathers its control words and data words lane-wise inside (8, 128) tiles
(``_gather_lanes``); the delta prefix sum is ``bitunpack.prefix_rows``.
Validated against the host reference decode in interpret mode across all
delta modes (tests/test_codecs_roundtrip).
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import deltas as core_deltas
from repro.core import streamvbyte as svb_lib
from repro.kernels import bitunpack as _bitunpack

LANES = 128


def _reconstruct(codes, offs, data, DW: int):
    """codes: (..., per) int32 2-bit byte-length codes; offs: (..., per)
    absolute byte offsets into the data stream; data: (DW,) uint32 words.
    Returns (..., per) uint32 values."""
    lens = codes + 1
    word = offs >> 2
    sh = ((offs & 3) << 3).astype(jnp.uint32)
    lo = jnp.take(data, jnp.clip(word, 0, DW - 1))
    hi = jnp.take(data, jnp.clip(word + 1, 0, DW - 1))
    val = (lo >> sh) | jnp.where(sh > 0, hi << ((jnp.uint32(32) - sh) & 31),
                                 jnp.uint32(0))
    nbits = (lens << 3).astype(jnp.uint32)
    mask = jnp.where(lens >= 4, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << jnp.minimum(nbits, 31)) - 1)
    return val & mask


def _codes_of(ctrl_flat, base: int, per: int):
    """Extract ``per`` 2-bit codes starting at value index ``base`` from a
    flat control-word vector (16 codes per word, LE byte order)."""
    i = base + jax.lax.broadcasted_iota(jnp.int32, (per, 1), 0).squeeze(-1)
    return ((jnp.take(ctrl_flat, i >> 4) >> ((i & 15) << 1)) & 3
            ).astype(jnp.int32)


@partial(jax.jit, static_argnames=("mode", "block_rows"))
def decode_svb(ctrl, data, doffs, seeds, mode: str, block_rows: int):
    """Batched jnp decode: ctrl (K, CW) uint32, data (DW,) uint32,
    doffs/seeds (K,).  Returns (K, block_rows, 128) uint32 values."""
    K = ctrl.shape[0]
    per = block_rows * LANES
    DW = data.shape[0]
    codes = _codes_of(ctrl.reshape(-1), 0, K * per).reshape(K, per)
    lens = codes + 1
    offs = doffs[:, None] + jnp.cumsum(lens, axis=1) - lens
    d = _reconstruct(codes, offs, data, DW).reshape(K, block_rows, LANES)
    return core_deltas.prefix_sum(d, seeds, mode)


def _gather_lanes(row, idx):
    """row[idx] per lane of a (1, 128) int32 row (idx in [0, 128)): Mosaic
    gathers within a vreg, so both are broadcast to one (8, 128) tile."""
    return jnp.take_along_axis(jnp.broadcast_to(row, (8, LANES)),
                               jnp.broadcast_to(idx, (8, LANES)),
                               axis=1)[:1]


def make_svb_kernel(mode: str, block_rows: int, cw: int):
    """One grid step decodes one block, one 128-value row at a time: the
    row's 8 control words give 128 2-bit codes, a lane scan of the byte
    lengths gives each value's byte offset, and the value's two data words
    come from the ≤ 3 consecutive 128-word data rows the row's bytes can
    span (a row holds at most 512 bytes)."""

    def kernel(doffs_ref, seeds_ref, ctrl_ref, data_ref, out_ref):
        k = pl.program_id(0)
        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        start = doffs_ref[k]                       # byte offset of the row
        for r in range(block_rows):
            cw0 = k * cw + 8 * r                   # first control word
            ctrl = lax.bitcast_convert_type(
                ctrl_ref[pl.ds(cw0 // LANES, 1), :], jnp.int32)
            word = _gather_lanes(ctrl, cw0 % LANES + lane // 16)
            lens = ((word >> ((lane % 16) * 2)) & 3) + 1
            offs = start + _bitunpack.lane_scan(lens) - lens
            q = (start >> 2) // LANES
            data = [lax.bitcast_convert_type(
                data_ref[pl.ds(q + i, 1), :], jnp.int32) for i in range(3)]

            def take(i):
                lo = _gather_lanes(data[0], i % LANES)
                mid = _gather_lanes(data[1], i % LANES)
                hi = _gather_lanes(data[2], i % LANES)
                return lax.bitcast_convert_type(
                    jnp.where(i < LANES, lo, jnp.where(i < 2 * LANES, mid, hi)),
                    jnp.uint32)

            li = (offs >> 2) - q * LANES
            sh = ((offs & 3) << 3).astype(jnp.uint32)
            lo, hi = take(li), take(li + 1)
            val = (lo >> sh) | jnp.where(
                sh > 0, hi << ((jnp.uint32(32) - sh) & 31), jnp.uint32(0))
            mask = jnp.where(lens >= 4, -1,
                             (1 << (jnp.minimum(lens, 3) << 3)) - 1)
            out_ref[pl.ds(r, 1), :] = lax.bitcast_convert_type(
                val, jnp.int32) & mask
            start = start + jnp.sum(lens)
        _bitunpack.prefix_rows(out_ref, 0, block_rows, seeds_ref[k], mode)

    return kernel


@partial(jax.jit, static_argnames=("mode", "block_rows", "interpret"))
def unpack_svb_blocks(ctrl, data, doffs, seeds, mode: str = "d1",
                      block_rows: int = svb_lib.DEFAULT_ROWS,
                      interpret: bool = True):
    """Pallas decode: same operands/result as ``decode_svb``.  The control
    and data streams stay resident in VMEM as (·, 128) rows (data padded by
    three zero rows so a row's last reads stay in bounds)."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.megakernel import compiler_params

    K, CW = ctrl.shape
    DW = int(data.shape[0])
    flat = ctrl.astype(jnp.uint32).reshape(-1)
    rc = -(-flat.shape[0] // LANES)
    flat = jnp.pad(flat, (0, rc * LANES - flat.shape[0]))
    rd = -(-DW // LANES) + 3
    words = jnp.pad(data.astype(jnp.uint32), (0, rd * LANES - DW))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # doffs, seeds → SMEM
        grid=(K,),
        in_specs=[pl.BlockSpec((rc, LANES), lambda k, *_: (0, 0)),
                  pl.BlockSpec((rd, LANES), lambda k, *_: (0, 0))],
        out_specs=pl.BlockSpec((None, block_rows, LANES),
                               lambda k, *_: (k, 0, 0)),
    )
    out = pl.pallas_call(
        make_svb_kernel(mode, block_rows, CW),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, block_rows, LANES), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="svb_decode",
    )(doffs.astype(jnp.int32),
      lax.bitcast_convert_type(seeds.astype(jnp.uint32), jnp.int32),
      flat.reshape(rc, LANES), words.reshape(rd, LANES))
    return lax.bitcast_convert_type(out, jnp.uint32)


def _pow2(n: int) -> int:
    size = 1
    while size < n:
        size *= 2
    return size


def decode_bucketed(sl) -> jnp.ndarray:
    """Decode an SVBList with (K, DW) padded to powers of two — bounds jit
    specializations exactly like ``bitpack.decode_bucketed``.  Pad blocks
    carry code 0 / offset 0 and decode to garbage the caller trims; pad
    data words are zero and only reachable through clamped gathers."""
    K = sl.num_blocks
    DW = int(sl.data.shape[0])
    Kp, DWp = _pow2(K), _pow2(DW)
    ctrl = np.zeros((Kp, sl.ctrl.shape[1]), np.uint32)
    ctrl[:K] = sl.ctrl
    data = np.zeros(DWp, np.uint32)
    data[:DW] = sl.data
    doffs = np.zeros(Kp, np.int32)
    doffs[:K] = sl.doffs
    maxes = np.zeros(Kp, np.uint32)
    maxes[:K] = sl.maxes
    maxes[K:] = sl.maxes[-1] if K else 0
    seeds = np.concatenate([[0], maxes[:-1]]).astype(np.uint32)
    vals = decode_svb(jnp.asarray(ctrl), jnp.asarray(data),
                      jnp.asarray(doffs), jnp.asarray(seeds),
                      sl.mode, sl.block_rows)
    return vals.reshape(-1)
