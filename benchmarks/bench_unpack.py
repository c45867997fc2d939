"""Paper Fig. 1a/1b: bit-unpacking speed, integrated vs two-pass (-NI)
differential coding, for every delta mode across bit widths.

Derived column: Gints/s and the integrated/NI speed ratio (Fig. 1a's y-axis).
The paper's claim to reproduce: integration helps most for the cheap-prefix
modes (D4/DM on SSE ↔ dv/dm here), and wider-stride modes decode faster.
"""

from __future__ import annotations

import numpy as np

from repro.core import bitpack
from benchmarks.common import emit, packed_fold_operands, timeit

N = 1 << 18                 # 64 blocks of 4096


def _list_with_width(rng, b: int, mode: str) -> bitpack.PackedList:
    """Sorted list whose per-block widths are ≈b for the given mode."""
    if b >= 30:
        gaps = rng.integers(1 << 24, 1 << 26, size=N)
    else:
        lo = max((1 << b) // 256, 1)
        gaps = rng.integers(lo, max(2 * lo, lo + 2), size=N)
    x = np.cumsum(gaps.astype(np.int64))
    x = x % (1 << 31)
    x = np.sort(np.unique(x))
    return bitpack.encode(x, mode=mode)


def fused_ab(quick: bool = False):
    """Fused-vs-staged decode A/B (ISSUE 7): staged = kernel-decode the
    WHOLE compressed list to a materialized array, then gallop-probe it;
    fused = the decode+intersect megakernel, which unpacks only the rare
    row's candidate blocks in kernel scratch.  The derived columns —
    decoded ints avoided and ns per decoded int — feed the codec
    autotuner cost table planned in ROADMAP.  Both sides run the kernel
    layer, so the comparison is mode-consistent (label = kernel_mode)."""
    import jax.numpy as jnp
    from repro.core import intersect as its
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    n = 1 << 16 if quick else 1 << 18
    for mode in (["d1"] if quick else ["d1", "dv"]):
        gaps = rng.integers(1, 64, size=n)
        x = np.unique(np.cumsum(gaps.astype(np.int64)) % (1 << 30))
        plist = bitpack.encode(x, mode=mode)
        # few, clustered probes: the skip regime where partial decode wins
        m = 8 if quick else 32
        r_np = np.sort(rng.choice(x[: len(x) // 4], m,
                                  replace=False)).astype(np.int32)
        r, valid, pk, active, c_pad = packed_fold_operands(r_np, plist)
        per = plist.block_rows * 128

        def staged():
            vals = ops.decode_packed(plist).astype(jnp.int32)
            return ops.intersect_gallop(r[0], vals)

        def fused():
            return ops.intersect_packed_fold(r, valid, pk, active,
                                             ops.row_tiles(r),
                                             mode=mode,
                                             block_rows=plist.block_rows)

        assert np.array_equal(
            np.asarray(fused()),
            np.asarray(staged()) & np.asarray(valid)), "A/B mismatch"
        t_staged = timeit(staged, reps=2)
        t_fused = timeit(fused, reps=2)
        dec_staged, dec_fused = plist.padded_n, c_pad * per
        emit(f"unpack/fused_ab/{mode}/staged", t_staged,
             f"{t_staged / dec_staged * 1e9:.2f} ns/int; "
             f"{dec_staged} decoded ints [{ops.kernel_mode()}]")
        emit(f"unpack/fused_ab/{mode}/fused", t_fused,
             f"{t_fused / dec_fused * 1e9:.2f} ns/int; "
             f"{dec_fused} decoded ints "
             f"({dec_staged - dec_fused} avoided, "
             f"{t_staged / t_fused:.1f}x) [{ops.kernel_mode()}]")


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    widths = [2, 8, 16] if quick else [1, 2, 4, 8, 12, 16, 20, 24]
    for mode in ["d1", "d2", "d4", "dm", "dv"]:
        for b in widths:
            pl = _list_with_width(rng, b, mode)
            bw = float(np.asarray(pl.widths).mean())
            t_int = timeit(lambda: bitpack.decode(pl))
            t_ni = timeit(lambda: bitpack.decode_ni(pl))
            gints = pl.padded_n / t_int / 1e9
            ratio = t_ni / t_int
            emit(f"unpack/{mode}/b{b}", t_int,
                 f"{gints:.3f} Gints/s; int/NI speedup {ratio:.2f}; "
                 f"avg width {bw:.1f}")
    fused_ab(quick)


if __name__ == "__main__":
    run()
