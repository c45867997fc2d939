"""Compile every served-path Pallas kernel for a TPU v5e without a chip.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached ``v5e:2x2`` topology, refusing what the chip would refuse:
block shapes off the (8, 128) tiling, unlowerable primitives (vector
gathers, value-level dynamic slices, cumsum), more VMEM than the scoped
limit.  Interpret mode accepts all of these, so the differential suites
cannot see them.  Shapes are the largest fused signature the full-size
ClueWeb09-B warm-up compiles (as ``chip_smoke.py`` prints it) with
``interpret=False``; each test asserts a Mosaic kernel (``tpu_custom_call``)
is in the compiled program.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and a test file that loaded it
while being collected would give pytest-xdist workers different tests.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.index import batch as batch_lib
from repro.kernels import bitunpack, intersect_gallop, megakernel
from repro.kernels import ops as kernel_ops
from repro.kernels import svb_decode

# largest fused svs signature of the full-size (50M-doc) warm-up on a v5e:
# seed rows M, fold rows N, J decoded folds, JB bitmaps of W words, as
# chip_smoke.py prints them, with Bp <= 2 there.  No
# packed family forms at that size; the packed shapes are a mid-size list
# (4096 word rows, 512 blocks, a 512-block candidate window).
B, M, N, J, JB, W = 8, 1 << 19, 1 << 21, 4, 4, 781250
JP, T_PAD, K_PAD, C_PAD, E_PAD, ROWS = 2, 4096, 512, 512, 8192, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


I32, U32, BOOL = jnp.int32, jnp.uint32, jnp.bool_


def _packed_shapes(lead):
    return [(lead + (T_PAD, 128), U32), (lead + (K_PAD,), I32),
            (lead + (K_PAD,), I32), (lead + (K_PAD,), U32),
            (lead + (C_PAD,), I32), (lead + (E_PAD,), I32),
            (lead + (E_PAD,), U32)]


def test_decoded_fold_compiles(one_chip):
    _compile(lambda r, v, f, a: megakernel.decoded_fold_batched(
        r, v, f, a, interpret=False), one_chip,
        ((B, M), I32), ((B, M), BOOL), ((J, B, N), I32), ((J, B), BOOL))


@pytest.mark.parametrize("mode", ["d1", "dm", "d4"])
def test_packed_fold_compiles(mode, one_chip):
    _compile(lambda r, v, *pk: megakernel.packed_fold_batched(
        r, v, *pk, mode=mode, block_rows=ROWS, interpret=False), one_chip,
        ((B, M), I32), ((B, M), BOOL), *_packed_shapes((JP, B)),
        ((JP, B), BOOL))


def test_gallop_tiles_batched_compiles(one_chip):
    _compile(lambda r, f: intersect_gallop.gallop_tiles_batched(
        r, f, interpret=False), one_chip, ((B, M), I32), ((B, N), I32))


def test_packed_gallop_batched_compiles(one_chip):
    _compile(lambda r, *pk: intersect_gallop.packed_gallop_batched(
        r, *pk, mode="d1", block_rows=ROWS, interpret=False), one_chip,
        ((B, M), I32), *_packed_shapes((B,)))


@pytest.mark.parametrize("mode", ["none", "d1", "d2", "dv"])
def test_unpack_blocks_compiles(mode, one_chip):
    _compile(lambda w, wi, s: bitunpack.unpack_blocks(
        w, wi, s, mode=mode, interpret=False), one_chip,
        ((512, 32, 128), U32), ((512,), I32), ((512,), U32))


@pytest.mark.parametrize("rows", [1, 8])
def test_unpack_svb_blocks_compiles(rows, one_chip):
    K = 512
    _compile(lambda c, d, o, s: svb_decode.unpack_svb_blocks(
        c, d, o, s, mode="d1", block_rows=rows, interpret=False), one_chip,
        ((K, rows * 8), U32), ((K * rows * 128,), U32), ((K,), I32),
        ((K,), U32))


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_sharded_group_program_compiles(backend, topo):
    """The sharded executor's group program over a 4-chip ('data',) mesh:
    rows split across chips with no all-gather, and on the pallas backend
    one megakernel per chip (XLA cannot partition a Mosaic kernel, so the
    program runs it per device over ``shard_map``).  The kernel mode is
    forced compiled: this process's default backend is the CPU."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))

    def shape(s, dtype, spec):
        return jax.ShapeDtypeStruct(s, dtype,
                                    sharding=NamedSharding(mesh, spec))

    args =(shape((B, M), I32, P("data")),
            shape((J, B, N), I32, P(None, "data")),
            shape((J, B), BOOL, P(None, "data")), None, None,
            shape((JB, B, W), U32, P(None, "data")))
    prev = kernel_ops.INTERPRET
    kernel_ops.set_kernel_mode("compiled")
    try:
        text = batch_lib._svs_program.lower(
            *args, algo="gallop", backend=backend, mode="d1",
            block_rows=ROWS, mesh=mesh).compile().as_text()
    finally:
        kernel_ops.INTERPRET = prev
    assert "all-gather" not in text
    assert ("tpu_custom_call" in text) == (backend == "pallas")
