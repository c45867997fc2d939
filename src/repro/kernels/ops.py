"""jit'd public wrappers around the Pallas kernels.

Kernel execution mode (DESIGN.md §2.12): the kernels target TPU v5e, so
on first use we *probe* the runtime backend — compiled Mosaic lowering
when ``jax.default_backend() == "tpu"``, Pallas interpret mode everywhere
else.  The probe is lazy: importing this module touches no backend.  The
REPRO_PALLAS_INTERPRET env var is an explicit override in either direction
(``0`` forces compiled, anything else forces interpret);
``set_kernel_mode("compiled"|"interpret"|"auto")`` re-resolves at runtime
(``serve.py --kernel-mode``).  ``INTERPRET`` stays the module-level switch
every wrapper reads at call time (None = not resolved yet), so
``ops.INTERPRET = ...`` assignments keep working.  Benchmarks must record
``kernel_mode()`` next to any Pallas number — interpret-mode timings are
not comparable to compiled ones.

VMEM overflow: a launch whose blocks do not fit the kernels' explicit
scoped-VMEM limit (``decoded_in_kernel`` / ``packed_in_kernel``) runs the jnp reference
instead, with identical results.  The batch launchers count such folds
with the same two functions on the same operands
(``stats["kernel_vmem_fallbacks"]``), so the fallback is never silent.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bitpack as core_bitpack
from repro.core import deltas as core_deltas
from repro.core.intersect import SENTINEL
from repro.kernels import bitunpack as _bitunpack
from repro.kernels import bitpack_pack as _bitpack_pack
from repro.kernels import intersect_gallop as _intersect_gallop
from repro.kernels import megakernel as _megakernel


def probe_kernel_mode() -> str:
    """Capability probe: can the runtime backend execute our Mosaic/TPU
    kernels natively?  Compiled only on TPU — the kernels use TPU grid
    semantics (sequential revisited output blocks, PrefetchScalarGridSpec),
    so GPU/CPU fall back to interpret.  Initializes the JAX backend."""
    return "compiled" if jax.default_backend() == "tpu" else "interpret"


def resolve_kernel_mode(mode: str = "auto") -> str:
    """Resolve a requested mode to 'compiled' | 'interpret'.  'auto' honors
    the REPRO_PALLAS_INTERPRET env override when set, else the probe."""
    if mode == "auto":
        env = os.environ.get("REPRO_PALLAS_INTERPRET")
        if env is not None:
            return "interpret" if env != "0" else "compiled"
        return probe_kernel_mode()
    if mode not in ("compiled", "interpret"):
        raise ValueError(f"unknown kernel mode {mode!r}")
    return mode


# None until first use: importing this module must not initialize a JAX
# backend (that would take the chip as a side effect of an import).
INTERPRET: bool | None = None


def _interpret() -> bool:
    global INTERPRET
    if INTERPRET is None:
        INTERPRET = resolve_kernel_mode() == "interpret"
    return INTERPRET


def kernel_mode() -> str:
    """The effective execution mode of every kernel wrapper in this module."""
    return "interpret" if _interpret() else "compiled"


def set_kernel_mode(mode: str = "auto") -> str:
    """Set the module-wide kernel mode; returns the resolved mode."""
    global INTERPRET
    INTERPRET = resolve_kernel_mode(mode) == "interpret"
    return kernel_mode()


ROWS = _bitunpack.ROWS
LANES = _bitunpack.LANES
decoded_fold_fits = _megakernel.decoded_fold_fits
packed_fold_fits = _megakernel.packed_fold_fits


def decoded_in_kernel(r, f) -> bool:
    """Where a decoded fold of seed rows ``r`` (..., M) against sorted
    lists ``f`` (..., N) runs: True in a Pallas kernel, False in the jnp
    reference (its blocks overflow the VMEM limit).  The one routing
    rule: the wrappers below branch on it and ``index.batch.count_folds``
    counts folds with it."""
    return decoded_fold_fits(r.shape[-1], f.shape[-1])


def packed_in_kernel(r, pk, block_rows: int) -> bool:
    """``decoded_in_kernel`` for a packed fold: ``pk`` is the operand tuple
    in ``batch._compose_pk`` order (words, widths, offsets, maxes, blk_ids,
    exc_pos, exc_add)."""
    words, widths, _, _, blk_ids, exc_pos, _ = pk
    return packed_fold_fits(r.shape[-1], words.shape[-2], widths.shape[-1],
                            blk_ids.shape[-1], exc_pos.shape[-1], block_rows)


# --------------------------------------------------------------------------
# padding helpers (flat packed words ↔ block-padded kernel layout)
# --------------------------------------------------------------------------

@jax.jit
def pad_packed(flat_words, offsets):
    """Gather flat (T,128) packed words into (K, 32, 128) block-padded form.
    T == 0 must short-circuit: ``clip(..., 0, T-1)`` would clamp to index
    -1 and ``jnp.take`` silently wraps negative indices, so an empty
    payload would gather garbage instead of zero blocks."""
    T = flat_words.shape[0]
    if T == 0:
        return jnp.zeros((offsets.shape[0], ROWS, LANES), flat_words.dtype)
    idx = jnp.clip(offsets[:, None] + jnp.arange(ROWS, dtype=jnp.int32)[None],
                   0, T - 1)
    return jnp.take(flat_words, idx, axis=0)


# --------------------------------------------------------------------------
# decode / encode
# --------------------------------------------------------------------------

def unpack_blocks(padded_words, widths, seeds, mode: str = "d1"):
    return _bitunpack.unpack_blocks(padded_words, widths, seeds, mode=mode,
                                    interpret=_interpret())


def decode_packed(plist: core_bitpack.PackedList) -> jnp.ndarray:
    """Kernel-path decode of a PackedList → flat padded values."""
    assert plist.block_rows == ROWS, \
        "Pallas kernels are specialized to 32-row (4096-int) blocks"
    padded = pad_packed(plist.flat_words, plist.offsets)
    seeds = core_bitpack.seeds_of(plist)
    vals = unpack_blocks(padded, plist.widths, seeds, mode=plist.mode)
    return vals.reshape(-1)


def decode_packed_ni(plist: core_bitpack.PackedList) -> jnp.ndarray:
    """Two-pass (-NI) kernel decode: unpack (mode='none') then a separate
    prefix-sum pass — the paper's Fig. 1a comparison point."""
    padded = pad_packed(plist.flat_words, plist.offsets)
    seeds = core_bitpack.seeds_of(plist)
    zero_seeds = jnp.zeros_like(seeds)
    d = unpack_blocks(padded, plist.widths, zero_seeds, mode="none")
    jax.block_until_ready(d)
    return core_deltas.prefix_sum(d, seeds, plist.mode).reshape(-1)


def pack_blocks(values, seeds, widths, mode: str = "d1"):
    """values: (K, 32, 128) uint32 sorted; returns (K, 32, 128) padded words."""
    d = core_deltas.encode_deltas_jnp(values, seeds, mode)
    return _bitpack_pack.pack_blocks_padded(d, widths, interpret=_interpret())


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, kv_len=None,
                    bq: int = 512, bk: int = 512):
    """Flash attention fwd (GQA-aware); see kernels/flash_attention.py."""
    from repro.kernels.flash_attention import flash_attention as _fa
    return _fa(q, k, v, causal=causal, kv_len=kv_len, bq=bq, bk=bk,
               interpret=_interpret())


# --------------------------------------------------------------------------
# intersection
# --------------------------------------------------------------------------

def intersect_gallop(r, f):
    """Kernel-path galloping intersection of one (M,) row against a sorted
    (N,) list; the jnp reference when f does not fit VMEM."""
    from repro.core import intersect as core_intersect
    M = r.shape[0]
    m_pad = (-M) % LANES
    if m_pad:
        r = jnp.concatenate([r, jnp.full((m_pad,), SENTINEL, jnp.int32)])
    if not decoded_in_kernel(r, f):
        return core_intersect.intersect_gallop(r, f)[:M]
    return _intersect_gallop.gallop_tiles(r, f, interpret=_interpret())[:M]


def intersect_gallop_batch(r, f):
    """Kernel-path batched galloping: r (B, M), f (B, N) → (B, M) mask.
    r must be sentinel-padded to M % 128 == 0 (index/batch.py buckets
    guarantee this); the vmapped jnp path when f does not fit VMEM."""
    if not decoded_in_kernel(r, f):
        from repro.core import intersect as core_intersect
        return core_intersect.intersect_gallop_batch(r, f)
    return _intersect_gallop.gallop_tiles_batched(r, f,
                                                  interpret=_interpret())


def intersect_packed_batch(r, words, widths, offsets, maxes, blk_ids,
                           exc_pos, exc_add, mode: str, block_rows: int):
    """Kernel-path batched packed gallop: decode only the candidate blocks of
    each row's compressed list in VMEM scratch, then probe the seed row
    against that window (one fused kernel; DESIGN.md §2.6).  The jnp path
    when the list and its window do not fit VMEM."""
    if not packed_in_kernel(r, (words, widths, offsets, maxes, blk_ids,
                                exc_pos, exc_add), block_rows):
        from repro.core import intersect as core_intersect
        return core_intersect.intersect_packed_batch(
            r, words, widths, offsets, maxes, blk_ids, exc_pos, exc_add,
            mode=mode, block_rows=block_rows)
    return _intersect_gallop.packed_gallop_batched(
        r, words, widths, offsets, maxes, blk_ids, exc_pos, exc_add,
        mode=mode, block_rows=block_rows, interpret=_interpret())


# --------------------------------------------------------------------------
# fused fold megakernels (DESIGN.md §2.12)
# --------------------------------------------------------------------------

def _fold_scan(r, valid, stack, active, intersect_fn):
    """VMEM-overflow path: the jnp reference folds under a lax.scan, same
    mask-fold semantics as the megakernels (and as
    ``batch._mask_fold_scan``, which this mirrors to avoid a circular
    import of the scheduler from the kernel layer)."""
    def step(v, xs):
        op, act = xs
        hit = intersect_fn(r, op)
        return v & jnp.where(act[:, None], hit, True), None

    valid, _ = lax.scan(step, valid, (stack, active))
    return valid


def intersect_fold_batch(r, valid, folds, fold_active):
    """Fused decoded SvS fold: ONE kernel launch ANDs the match masks of the
    whole (J, B, N) fold stack into ``valid`` (grid (B, J), the output mask
    block revisited across j).  The jnp reference when a fold row does not
    fit VMEM."""
    if folds.shape[0] == 0:
        return valid
    if not decoded_in_kernel(r, folds):
        from repro.core import intersect as core_intersect
        return _fold_scan(r, valid, folds, fold_active,
                          core_intersect.intersect_gallop_batch)
    return _megakernel.decoded_fold_batched(r, valid, folds, fold_active,
                                            interpret=_interpret())


def intersect_packed_fold(r, valid, pk, pk_active, mode: str,
                          block_rows: int):
    """Fused packed SvS fold: ONE kernel launch decodes each (j, b) slot's
    candidate blocks in VMEM scratch and ANDs the match masks of the whole
    (Jp, B, ...) packed stack into ``valid`` — no materialized decoded
    array (DESIGN.md §2.12).  ``pk`` is the stacked operand tuple in
    ``batch._compose_pk`` order.  The jnp reference when one slot's
    compressed list and decode window do not fit VMEM."""
    words, widths, offsets, maxes, blk_ids, exc_pos, exc_add = pk
    if words.shape[0] == 0:
        return valid
    if not packed_in_kernel(r, pk, block_rows):
        from repro.core import intersect as core_intersect
        return _fold_scan(
            r, valid, pk, pk_active,
            lambda rr, op: core_intersect.intersect_packed_batch(
                rr, *op, mode=mode, block_rows=block_rows))
    return _megakernel.packed_fold_batched(
        r, valid, words, widths, offsets, maxes, blk_ids, exc_pos, exc_add,
        pk_active, mode=mode, block_rows=block_rows, interpret=_interpret())
