"""Pallas kernels vs pure-jnp oracles (interpret mode), swept over shapes,
bit widths and delta modes — the per-kernel allclose requirement."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitpack, deltas as deltas_lib
from repro.core import intersect as its
from repro.kernels import ops, ref
from repro.kernels import bitunpack as kb


MODES = ["none", "d1", "d2", "d4", "dm", "dv"]


def _make_block_with_width(rng, b: int):
    """One (32,128) block whose deltas need exactly width b."""
    if b == 0:
        d = np.zeros((1, 32, 128), np.uint32)
    else:
        d = rng.integers(0, 1 << b, size=(1, 32, 128)).astype(np.uint32)
        d[0, 0, 0] = (1 << b) - 1            # force the max
    return d


@pytest.mark.parametrize("b", list(range(0, 33)))
def test_unpack_kernel_all_widths(b, rng):
    """Width sweep: pack on host, unpack via kernel vs jnp oracle."""
    d = _make_block_with_width(rng, b)
    packed = bitpack.pack_block_np(d[0], b)
    padded = np.zeros((1, 32, 128), np.uint32)
    padded[0, : packed.shape[0]] = packed
    widths = jnp.asarray([b], jnp.int32)
    seeds = jnp.asarray([0], jnp.uint32)
    got = ops.unpack_blocks(jnp.asarray(padded), widths, seeds, mode="none")
    want = ref.unpack_blocks_ref(jnp.asarray(padded), widths, seeds,
                                 mode="none")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got), d)


@pytest.mark.parametrize("mode", MODES)
def test_unpack_kernel_integrated_prefix(mode, rng):
    """Integrated unpack+prefix-sum (Algorithm 1) vs library decode."""
    x = np.cumsum(rng.integers(1, 2000, size=5 * 4096 + 123))
    pl = bitpack.encode(x, mode=mode)
    got = np.asarray(ops.decode_packed(pl))[: pl.n]
    assert np.array_equal(got, x)
    got_ni = np.asarray(ops.decode_packed_ni(pl))[: pl.n]
    assert np.array_equal(got_ni, x)


@pytest.mark.parametrize("mode", ["d1", "d4", "dm", "dv"])
def test_pack_kernel_roundtrip(mode, rng):
    x = np.cumsum(rng.integers(1, 300, size=4 * 4096)).astype(np.int64)
    blocks = x.reshape(4, 32, 128)
    maxes = blocks[:, -1, -1]
    seeds_np = np.concatenate([[0], maxes[:-1]]).astype(np.int64)
    d = deltas_lib.encode_deltas_np(blocks, seeds_np, mode)
    widths = jnp.asarray(
        [int(d[k].max()).bit_length() for k in range(4)], jnp.int32)
    seeds = jnp.asarray(seeds_np.astype(np.uint32))
    packed_k = ops.pack_blocks(jnp.asarray(blocks.astype(np.uint32)),
                               seeds, widths, mode=mode)
    packed_r = ref.pack_blocks_ref(jnp.asarray(d), widths)
    assert np.array_equal(np.asarray(packed_k), np.asarray(packed_r))
    vals = ops.unpack_blocks(packed_k, widths, seeds, mode=mode)
    assert np.array_equal(np.asarray(vals), blocks.astype(np.uint32))


@pytest.mark.parametrize("m,n", [(64, 1024), (500, 65536), (128, 1 << 18)])
def test_intersect_kernel_sweep(m, n, rng):
    inter = np.sort(rng.choice(2**25, size=m // 3, replace=False))
    r = np.union1d(inter, rng.choice(2**25, size=m, replace=False))
    f = np.union1d(inter, rng.choice(2**25, size=n, replace=False))
    expect = its.intersect_ref(r, f)
    mask_k = ops.intersect_gallop(jnp.asarray(r, jnp.int32),
                                  jnp.asarray(f, jnp.int32))
    rp = jnp.asarray(r, jnp.int32)
    vals, cnt = its.compact(rp, mask_k)
    assert np.array_equal(np.asarray(vals)[: int(cnt)], expect)
    # oracle agreement
    M = its.pow2_bucket(len(r))
    N = its.pow2_bucket(len(f), floor=1024)
    mask_o = ref.intersect_gallop_ref(jnp.asarray(its.pad_to(r, M)),
                                      jnp.asarray(its.pad_to(f, N)))
    assert np.array_equal(np.asarray(mask_k), np.asarray(mask_o)[: len(r)])


def test_kernel_vmem_budget():
    """The fit predicates are derived from the explicit scoped-VMEM limit,
    which itself stays under TPU v5e's 128 MiB of VMEM: double-buffered
    (rows, 128) int32 blocks (sublanes padded to 8) plus scratch plus the
    headroom must fit, and one int past the limit must not."""
    from repro.kernels import megakernel as mk
    assert mk.VMEM_LIMIT_BYTES < 128 * 2**20
    unpack_ws = 2 * 2 * 32 * 128 * 4               # in+out tiles, 2 buffers
    assert mk.fits_vmem([32, 32])
    assert unpack_ws < mk.VMEM_LIMIT_BYTES
    budget = mk.VMEM_LIMIT_BYTES - mk.VMEM_HEADROOM_BYTES
    m = 1 << 14                                    # seed row + 2 mask rows
    fixed = 2 * 3 * m * 4
    n_max = (budget - fixed) // (2 * 4)            # largest fold that fits
    n_max -= n_max % 1024
    assert ops.decoded_fold_fits(m, n_max)
    assert not ops.decoded_fold_fits(m, n_max + 1024)
    # a 1-row tile pads to 8 sublanes
    assert mk._tile_bytes(1) == mk._tile_bytes(8) == 8 * kb.LANES * 4
    # the packed window is single-buffered scratch on top of the blocks
    assert ops.packed_fold_fits(m, 1024, 256, 64, 0, 32)
    assert not ops.packed_fold_fits(m, 1024, 256, 1 << 16, 0, 32)


def test_pad_packed_empty_payload():
    """Regression (ISSUE 5): with T == 0 flat words the old
    clip(..., 0, T-1) produced index -1 and jnp.take silently wrapped;
    the empty case must return all-zero blocks of the right shape."""
    flat = jnp.zeros((0, kb.LANES), jnp.uint32)
    for K in (0, 3):
        out = ops.pad_packed(flat, jnp.zeros((K,), jnp.int32))
        assert out.shape == (K, ops.ROWS, kb.LANES)
        assert not np.asarray(out).any()
    # non-empty payloads are untouched by the guard
    flat = jnp.arange(2 * kb.LANES, dtype=jnp.uint32).reshape(2, kb.LANES)
    out = ops.pad_packed(flat, jnp.zeros((1,), jnp.int32))
    assert np.array_equal(np.asarray(out[0, :2]), np.asarray(flat))
    assert np.array_equal(np.asarray(out[0, 2:]),
                          np.broadcast_to(np.asarray(flat[1]),
                                          (ops.ROWS - 2, kb.LANES)))
