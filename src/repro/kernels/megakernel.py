"""Pallas TPU megakernels: the whole SvS fold chain in ONE launch.

A fused group program folds J decoded and Jp packed lists into each seed
row's validity mask.  The megakernels here run that fold as one kernel
grid (DESIGN.md §2.12):

* ``decoded_fold_batched`` — grid (B, J).  Step (b, j) probes seed row b
  against decoded fold j (``intersect_gallop.probe_tiles``) and ANDs the
  match mask into the row's running validity mask.
* ``packed_fold_batched`` — grid (B, Jp).  Step (b, j) decodes the
  candidate blocks of row b's j-th *compressed* list into VMEM scratch
  (``bitunpack.decode_candidates`` — the same shift/mask rows as the
  Algorithm-1 unpack kernel), patches FastPFOR exceptions, prefix-sums
  deltas, and probes the seed row against the scratch window.  No decoded
  array is ever materialized in HBM: decode volume per step is C·block
  ints of VMEM scratch.

Both kernels accumulate into the same output block: the out BlockSpec maps
every j to row b's mask block, the innermost grid axis revisits it J
times, and ``pl.when(j == 0)`` seeds it from the incoming validity mask.
TPU grids execute sequentially with the last axis innermost, so the
revisited block stays resident in VMEM across the J steps and is flushed
once per row — the mask-fold contract of DESIGN.md §2.10 moved inside the
kernel.  Inactive (j, b) slots (fused arity ceilings pad J/Jp past each
row's real fold count) skip the step: their mask contribution is the
identity, exactly like the host-side ``_mask_fold_scan``.

Each step walks only the seed row's real tiles: a (B,) extent of
ceil(seed_n / 128) tiles per row rides in scalar prefetch next to the
fold activity, and ``probe_tiles`` stops there.  Past it every tile is
SENTINEL and already invalid, so a row costs its seed's tiles, not the
M/128 of the family ceiling it is padded to, and the answer is the same
byte for byte.  ``row_tiles`` is the full extent, for callers that know
no seed lengths.

Layout: rows of M values are passed as (M/128, 128) tiles and masks as
int32 0/1 (Mosaic blocks are lane-dense and carry no booleans); per-list
metadata vectors are padded to whole 128-lane rows; fold activity and the
row extents ride in scalar prefetch.  Every kernel runs under the
explicit scoped-VMEM limit ``VMEM_LIMIT_BYTES``; ``decoded_fold_fits`` /
``packed_fold_fits`` say from the shapes alone whether a launch fits it,
and ``kernels.ops`` routes a launch that does not to the jnp reference.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitpack as core_bitpack
from repro.kernels import bitunpack as _bitunpack
from repro.kernels.intersect_gallop import BLOCK_ROWS, SENTINEL, probe_tiles

LANES = core_bitpack.LANES

# Scoped-VMEM limit every kernel is compiled with.  TPU v5e has 128 MiB of
# VMEM per core; the compiler's default scoped limit is far lower, so the
# kernels ask for this much explicitly and size their blocks against it.
VMEM_LIMIT_BYTES = 96 << 20
# Room left for Mosaic's own temporaries (the (128, 128) compare tiles,
# spills) on top of the blocks and scratch the budget counts.
VMEM_HEADROOM_BYTES = 8 << 20


def compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _rows(n: int) -> int:
    """(·, 128) rows of an n-int vector, as the kernels lay it out."""
    return max(-(-n // LANES), 1)


def _tile_bytes(rows: int) -> int:
    """VMEM bytes of a (rows, 128) 32-bit block: sublanes pad to 8."""
    return -(-rows // 8) * 8 * LANES * 4


def fits_vmem(block_rows: list[int], scratch_rows: int = 0) -> bool:
    """True when double-buffered blocks of these (rows, 128) shapes plus
    single-buffered scratch fit the scoped VMEM limit."""
    need = (2 * sum(_tile_bytes(r) for r in block_rows)
            + (_tile_bytes(scratch_rows) if scratch_rows else 0))
    return need <= VMEM_LIMIT_BYTES - VMEM_HEADROOM_BYTES


def _fold_rows(n: int) -> int:
    """Rows of a decoded fold: whole (8, 128) probe blocks."""
    return -(-max(n, BLOCK_ROWS * LANES) // (BLOCK_ROWS * LANES)) * BLOCK_ROWS


def decoded_fold_fits(m: int, n: int) -> bool:
    """Does one (b, j) step of ``decoded_fold_batched`` — seed row, incoming
    and running masks of m ints, one decoded fold of n ints — fit VMEM?"""
    return fits_vmem([_rows(m)] * 3 + [_fold_rows(n)])


def packed_fold_fits(m: int, t_pad: int, k_pad: int, c_pad: int,
                     e_pad: int, block_rows: int) -> bool:
    """Does one (b, j) step of ``packed_fold_batched`` — seed/mask rows,
    one list's compressed words and metadata, and the decoded candidate
    window in scratch — fit VMEM?"""
    return fits_vmem([_rows(m)] * 3 + [t_pad] + [_rows(k_pad)] * 3
                     + [_rows(c_pad)] + [_rows(max(e_pad, 1))] * 2,
                     scratch_rows=c_pad * block_rows)


def _as_rows(x, fill):
    """Pad the last axis to whole 128-lane rows (at least one) and split it:
    (..., n) → (..., rows, 128)."""
    n = x.shape[-1]
    rows = _rows(n)
    if rows * LANES != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, rows * LANES - n)]
        x = jnp.pad(x, pad, constant_values=fill)
    return x.reshape(x.shape[:-1] + (rows, LANES))


def row_tiles(r):
    """The full extent of seed rows ``r`` (B, M): M/128 tiles each."""
    return jnp.full(r.shape[:1], r.shape[-1] // LANES, jnp.int32)


def _i32(x):
    return (lax.bitcast_convert_type(x, jnp.int32) if x.dtype == jnp.uint32
            else x.astype(jnp.int32))


# --------------------------------------------------------------------------
# decoded folds: unpacked short lists, one probe per (row, fold)
# --------------------------------------------------------------------------

def _decoded_fold_kernel(act_ref, ext_ref, r_ref, v_ref, f_ref, out_ref):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _seed_mask():
        out_ref[...] = v_ref[...]

    @pl.when(act_ref[j * pl.num_programs(0) + b] != 0)
    def _fold():
        probe_tiles(r_ref, f_ref, out_ref, ext_ref[b])


@partial(jax.jit, static_argnames=("interpret",))
def decoded_fold_batched(r, valid, folds, fold_active, seed_tiles,
                         interpret: bool = True):
    """Fused decoded SvS fold: r (B, M) sentinel-padded int32, M % 128 == 0;
    valid (B, M) bool, False on every SENTINEL slot; folds (J, B, N)
    sorted, sentinel-padded; fold_active (J, B); seed_tiles (B,) int32,
    the 128-lane tiles of each row that hold real values (``row_tiles``
    for all of them).  Returns the (B, M) validity mask after ANDing all J
    match masks — one kernel launch for the whole stack."""
    J, B, N = folds.shape
    M = r.shape[-1]
    nf = _fold_rows(N) * LANES
    if nf != N:
        folds = jnp.pad(folds, ((0, 0), (0, 0), (0, nf - N)),
                        constant_values=SENTINEL)
    tm = M // LANES
    row = lambda b, j, act, ext: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # fold activity and
        grid=(B, J),                                 # row extents → SMEM;
        in_specs=[                                   # j innermost: the out
            pl.BlockSpec((None, tm, LANES), row),    # block is revisited
            pl.BlockSpec((None, tm, LANES), row),
            pl.BlockSpec((None, None, nf // LANES, LANES),
                         lambda b, j, act, ext: (j, b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tm, LANES), row),
    )
    out = pl.pallas_call(
        _decoded_fold_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, tm, LANES), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="decoded_fold",
    )(fold_active.astype(jnp.int32).reshape(-1),
      seed_tiles.astype(jnp.int32),
      r.astype(jnp.int32).reshape(B, tm, LANES),
      valid.astype(jnp.int32).reshape(B, tm, LANES),
      folds.astype(jnp.int32).reshape(J, B, nf // LANES, LANES))
    return out.reshape(B, M) != 0


# --------------------------------------------------------------------------
# packed folds: decode + intersect fused, no materialized decoded array
# --------------------------------------------------------------------------

def make_packed_fold_kernel(mode: str, block_rows: int, k_pad: int,
                            n_cand: int, n_exc: int):
    def kernel(act_ref, ext_ref, r_ref, v_ref, w_ref, wid_ref, off_ref,
               max_ref, blk_ref, ep_ref, ea_ref, out_ref, win_ref):
        b, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _seed_mask():
            out_ref[...] = v_ref[...]

        @pl.when(act_ref[j * pl.num_programs(0) + b] != 0)
        def _fold():
            _bitunpack.decode_candidates(
                w_ref, wid_ref, off_ref, max_ref, blk_ref, ep_ref, ea_ref,
                win_ref, mode=mode, block_rows=block_rows, k_pad=k_pad,
                n_cand=n_cand, n_exc=n_exc)
            probe_tiles(r_ref, win_ref, out_ref, ext_ref[b])
    return kernel


@partial(jax.jit, static_argnames=("mode", "block_rows", "interpret"))
def packed_fold_batched(r, valid, words, widths, offsets, maxes, blk_ids,
                        exc_pos, exc_add, active, seed_tiles, mode: str,
                        block_rows: int, interpret: bool = True):
    """Fused packed SvS fold.  r (B, M) sentinel-padded int32; valid (B, M)
    bool, False on every SENTINEL slot; words (Jp, B, Tp, 128) uint32;
    widths/offsets/maxes (Jp, B, Kp); blk_ids (Jp, B, C) ascending, padded
    with Kp; exc_pos / exc_add (Jp, B, E) FastPFOR patches (-1-padded);
    active (Jp, B); seed_tiles (B,) int32 as in ``decoded_fold_batched``.
    Returns the (B, M) validity mask after folding all Jp packed lists —
    one kernel launch, decode scratch only, no decoded array in HBM."""
    Jp, B, Tp, _ = words.shape
    M = r.shape[-1]
    Kp = widths.shape[-1]
    C = blk_ids.shape[-1]
    E = exc_pos.shape[-1]
    tm = M // LANES
    row = lambda b, j, act, ext: (b, 0, 0)
    jb = lambda b, j, act, ext: (j, b, 0, 0)
    meta = [_as_rows(_i32(x), 0) for x in (widths, offsets, maxes)]
    blk = _as_rows(_i32(blk_ids), Kp)
    ep = _as_rows(_i32(exc_pos), -1)
    ea = _as_rows(_i32(exc_add), 0)
    operands = [words.astype(jnp.uint32), *meta, blk, ep, ea]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # activity, extents
        grid=(B, Jp),                                # j innermost: the out
        in_specs=[pl.BlockSpec((None, tm, LANES), row)] * 2   # revisited
        + [pl.BlockSpec((None, None) + x.shape[2:], jb) for x in operands],
        out_specs=pl.BlockSpec((None, tm, LANES), row),
        scratch_shapes=[pltpu.VMEM((C * block_rows, LANES), jnp.int32)],
    )
    out = pl.pallas_call(
        make_packed_fold_kernel(mode, block_rows, Kp, C, E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, tm, LANES), jnp.int32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="packed_fold",
    )(active.astype(jnp.int32).reshape(-1), seed_tiles.astype(jnp.int32),
      r.astype(jnp.int32).reshape(B, tm, LANES),
      valid.astype(jnp.int32).reshape(B, tm, LANES), *operands)
    return out.reshape(B, M) != 0
