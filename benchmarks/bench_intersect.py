"""Paper Fig. 2a/2b: intersection speed across cardinality ratios.

Lists built exactly as §6.6: target |f| = n, |r| = n/ratio, guaranteed
intersection ≥ m/3, ClusterData distribution in [0, 2^26).  Baseline is
numpy's C merge (np.intersect1d) standing in for the paper's SCALAR.
Derived: relative speed vs SCALAR (the paper's y-axis) — used to re-derive
the V1/galloping dispatch thresholds (TILED_MAX_RATIO) on this platform.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import bitpack
from repro.core import intersect as its
from repro.data.clusterdata import paired_lists
from benchmarks.common import emit, packed_fold_operands, timeit


def fused_ab(quick: bool = False):
    """Fused-vs-staged intersection A/B (ISSUE 7), at the high cardinality
    ratios where the galloping regime lives: staged = kernel-decode the
    whole long list then gallop-probe the materialized array; fused = the
    decode+intersect megakernel (candidate blocks unpacked in kernel
    scratch, no materialized array).  Reports ns per rare-list int and the
    decoded ints the fused path avoids — cost-table inputs for the codec
    autotuner planned in ROADMAP."""
    from repro.kernels import ops

    rng = np.random.default_rng(9)
    n = 1 << 18 if quick else 1 << 20
    for ratio in ([1024] if quick else [256, 4096]):
        m = max(n // ratio, 4)
        r_l, f = paired_lists(rng, m, n)
        pf = bitpack.encode(f, mode="d1")
        r, valid, pk, active, c_pad = packed_fold_operands(
            np.asarray(r_l, np.int32), pf)
        per = pf.block_rows * 128

        def staged():
            vals = ops.decode_packed(pf).astype(jnp.int32)
            return ops.intersect_gallop(r[0], vals)

        def fused():
            return ops.intersect_packed_fold(r, valid, pk, active,
                                             ops.row_tiles(r),
                                             mode="d1",
                                             block_rows=pf.block_rows)

        assert np.array_equal(
            np.asarray(fused()),
            np.asarray(staged()) & np.asarray(valid)), "A/B mismatch"
        t_staged = timeit(staged, reps=2)
        t_fused = timeit(fused, reps=2)
        avoided = pf.padded_n - c_pad * per
        emit(f"intersect/fused_ab/r{ratio}/staged", t_staged,
             f"{t_staged / m * 1e9:.0f} ns/r-int; {pf.padded_n} decoded "
             f"ints [{ops.kernel_mode()}]")
        emit(f"intersect/fused_ab/r{ratio}/fused", t_fused,
             f"{t_fused / m * 1e9:.0f} ns/r-int; {c_pad * per} decoded "
             f"ints ({avoided} avoided, {t_staged / t_fused:.1f}x) "
             f"[{ops.kernel_mode()}]")


def run(quick: bool = False):
    rng = np.random.default_rng(2)
    n = 1 << 18 if quick else 1 << 20
    ratios = [1, 16, 256] if quick else [1, 4, 16, 64, 256, 1024, 4096]
    for ratio in ratios:
        m = max(n // ratio, 4)
        r, f = paired_lists(rng, m, n)
        t_scalar = timeit(lambda: np.intersect1d(r, f), reps=2)

        M = its.pow2_bucket(len(r))
        N = its.pow2_bucket(len(f), floor=1024)
        rp = jnp.asarray(its.pad_to(r, M))
        fp = jnp.asarray(its.pad_to(f, N))
        pf = bitpack.encode(f, mode="d1")

        algos = [
            ("tiled", lambda: its.intersect_tiled(
                rp, fp, tile_r=min(128, M), tile_f=min(1024, N))),
            ("gallop", lambda: its.intersect_gallop(rp, fp)),
            ("auto", lambda: its.intersect_auto(rp, fp, len(r), len(f))),
        ]
        if ratio >= 64:
            # packed-gallop decodes one block per r element: it is the
            # high-ratio algorithm (paper's galloping regime); at low ratios
            # it does m×4096 decode work by construction — skipped, and the
            # skip is the documented behaviour of the dispatch heuristic.
            algos.insert(2, ("packed-gallop",
                             lambda: its.intersect_packed(rp, pf)))
        for name, fn in algos:
            t = timeit(fn)
            emit(f"intersect/r{ratio}/{name}", t,
                 f"{t_scalar / t:.2f}x vs scalar; m={m} n={n}")
    fused_ab(quick)


if __name__ == "__main__":
    run()
