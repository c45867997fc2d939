"""Pallas TPU kernel: block bit packing (encode side of paper §3).

One grid step packs one (32, 128) delta tile into a (32, 128) word tile whose
first ``b`` rows are the packed words (the rest zero) — the block-padded
mirror of the unpack kernel.  Delta computation happens in the jnp wrapper
(``ops.pack_blocks``): 'computing deltas during compression is an inexpensive
operation' (paper §4); the kernel is the bit-shuffle hot loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 32
LANES = 128


def pack_kernel(widths_ref, deltas_ref, out_ref):
    """OR each delta row into the word rows it straddles, read-modify-write
    on the output ref at the row's dynamic word index."""
    k = pl.program_id(0)
    width = widths_ref[k]
    b = width.astype(jnp.uint32)
    out_ref[...] = jnp.zeros((ROWS, LANES), jnp.uint32)
    for r in range(ROWS):                          # static unroll
        start = r * width
        w = start >> 5
        sh = (start & 31).astype(jnp.uint32)
        val = deltas_ref[r:r + 1, :]
        out_ref[pl.ds(w, 1), :] = out_ref[pl.ds(w, 1), :] | (val << sh)
        w1 = jnp.minimum(w + 1, ROWS - 1)
        spill = jnp.where(sh + b > 32, val >> ((jnp.uint32(32) - sh) & 31),
                          jnp.uint32(0))
        out_ref[pl.ds(w1, 1), :] = out_ref[pl.ds(w1, 1), :] | spill


@partial(jax.jit, static_argnames=("interpret",))
def pack_blocks_padded(deltas, widths, interpret: bool = True):
    """deltas: (K, 32, 128) uint32 (< 2**width per block); widths: (K,).
    Returns (K, 32, 128) uint32 block-padded packed words."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.megakernel import compiler_params

    K = deltas.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[pl.BlockSpec((None, ROWS, LANES), lambda k, *_: (k, 0, 0))],
        out_specs=pl.BlockSpec((None, ROWS, LANES), lambda k, *_: (k, 0, 0)),
    )
    return pl.pallas_call(
        pack_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, ROWS, LANES), jnp.uint32),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="pack_blocks",
    )(widths.astype(jnp.int32), deltas.astype(jnp.uint32))
