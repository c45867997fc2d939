"""Batched vs sequential query-engine throughput + partial-decode accounting
(ISSUE 1 + ISSUE 2 + ISSUE 3 + ISSUE 4 acceptance gates).

Replays a Table-2-shaped query log (2–5 terms, skewed per-position list
lengths) through the sequential engine (one device dispatch per fold, host
round-trips between terms) and the shape-bucketed batched scheduler at
several batch sizes.  Two regimes, as in the paper:

  * cached   — Table 4: SvS over already-decoded lists (DecodeCache on both
               paths); isolates intersection + dispatch, which is what the
               batched engine accelerates.  Gate: ≥ 2× at batch ≥ 32.
  * uncached — Table 5: no decoded-value cache.  Since ISSUE 3 the batched
               numbers measure the *serving fast path*: the device-resident
               index (``source.ResidentPool``, staged once untimed at
               build) plus pipelined dispatch — per-batch host decode /
               pow2 padding / H2D staging is gone, which is where ~70% of
               the uncached batch time went.  The sequential columns stay
               pool-less as the reference.  ``batched_b32_host_staged``
               keeps the old per-batch host-staging path as the A/B point.

Both regimes cover the pallas backend at b32, and the pipelined executor
(depth 2) is asserted byte-identical to the sequential engine on both
backends before it is timed (ISSUE 3 gate: uncached/batched_b32_qps ≥ 1.5×
the PR-2 baseline of 258.6).  Since ISSUE 7 every Pallas number carries an
explicit ``<key>_kernel_mode`` field ("compiled" | "interpret", from
``kernels.ops.kernel_mode()``): this container runs the kernels in
*interpret mode*, whose cost scales with the number of interpreted
kernel-grid invocations, so interpret timings measure the Pallas
interpreter, not the engine — ``compare`` refuses to ratio-gate a key
whose mode differs from the baseline, and ``--max-pallas-ratio`` is
advisory unless the run was compiled (DESIGN.md §2.12).  The jax-backend
columns are the load-bearing throughput gates.  The pallas backend itself
runs the fused decode+intersect megakernels (one launch per fold stack);
an interpret-mode occupancy guard (``batch.PALLAS_MIN_OCCUPANCY``)
demotes sparsely occupied fused chunks to the jax program, which is why
the interpret pallas columns track the jax ones at low occupancy.

A third section replays a *skewed-ratio* log (tiny first term, very long
second term) and reports decoded-ints/query with the posting-source skip
path off vs on (``execute_batch(skip=...)``): the ISSUE 2 gate is a ≥ 5×
drop while results stay byte-identical to the sequential engine on both
backends.  This section runs pool-less on purpose — it gates the
partial-decode machinery itself, which residency would mask.

A fourth section measures the sharded fan-out (``repro.index.shard``,
DESIGN.md §2.9) at shards ∈ {1, 2, 4} in a *device-compute-bound* regime
(mid-size seeds, several long lists → large candidate-block partial
decodes per row).  It runs in-process over the devices the process has;
CI launches the benchmark under
``--xla_force_host_platform_device_count=4`` so four host-platform devices
exist (the single-device sections above use the first).  The scaling
gate is >1.5× batched throughput at 4 shards vs 1 in the full-size run
(``sharded/speedup_s4`` in BENCH_engine.json); the smoke variant reports
the same keys but is too small to gate on — scheduler-bound regimes
measure the host, not the sharding.

A fifth section (``dispatch/``, ISSUE 5) A/Bs megagroup fusion on the
mixed-signature corpus: device dispatches per batch fused vs unfused
(gate: ≥ 4× reduction), the AOT warmup compile count, the steady-state
compile count after warmup (must be 0), and the fused/unfused throughput
delta with everything else held fixed.

A sixth section (``latency/``, ISSUE 6) measures *open-loop* serving: the
continuous-batching server (``repro.launch.server``) fed by Poisson and
bursty arrival processes at offered loads derived from the same run's
measured drain capacity (0.5× and 0.8×) — closed-loop q/s says nothing
about the p99 a user sees under arrival jitter.  Reported per load:
p50/p99/p999 end-to-end latency, p99 time-in-queue, the max queue-depth
bucket, and the shed count; the drain run doubles as the acceptance
check that a warmed steady-state server compiles nothing and returns
byte-identical results to the offline batched path.  ``--max-p99-ms``
gates ``latency/p99_ms`` (Poisson at half capacity — a same-run-derived
load, so the gate tracks the engine's latency behavior, not the absolute
speed of the runner).

A seventh section (``mutation/``, ISSUE 9) serves the segmented mutable
index (DESIGN.md §2.14) after a burst of adds/seals/deletes: steady-state
q/s vs q/s *during a background merge* (``mutation/merge_ratio`` — the
serving cost of compaction), both gated byte-identical against a
rebuild-from-scratch build, with ``mutation/steady_compiles`` asserting
the post-swap generation compiles nothing.

Derived column reports queries/sec (and decoded ints/query where that is
the figure of merit).  CLI: ``--smoke`` runs the reduced sweep standalone
(CI smoke gate), ``--json PATH`` additionally records a machine-readable
baseline (BENCH_engine.json / BENCH_engine_smoke.json), ``--compare PATH``
prints per-key deltas vs a committed baseline, ``--max-regress PCT``
turns the comparison into a CI gate: it fails if the batched-over-
sequential *speedup* at b32 (cached regime) regressed by more than PCT —
the ratio of two same-run numbers, so the gate tracks the engine, not the
absolute speed of the runner it happens to execute on.  ``--max-dispatches
N`` gates the fused dispatches-per-batch count the same way (a regression
back to per-signature dispatch fails fast), and ``--profile`` prints the
per-batch schedule / assemble / dispatch / device breakdown of the fused
resident pipeline.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.common import emit

RESULTS: dict[str, float | str] = {}

# the --max-regress gate compares this speedup ratio (see module docstring)
GATE_NUM = "cached/batched_b32_qps"
GATE_DEN = "cached/sequential_qps"

# the --max-pallas-ratio gate compares this same-run jax/pallas throughput
# ratio on the fused packed (skewed) family; it hard-gates only when the
# kernels ran compiled — interpret numbers are advisory (see _gate_pallas)
PALLAS_GATE = "skewed/pallas_vs_jax_ratio"
PALLAS_GATE_MODE = "skewed/batched_pallas_kernel_mode"


def _kernel_mode() -> str:
    """Execution mode of every Pallas number this run records.  Stored as
    an explicit ``<key>_kernel_mode`` field next to each Pallas entry —
    interpret-mode timings measure the Pallas interpreter, not the
    hardware, and must never be ratio-gated against compiled ones
    (``compare`` refuses; DESIGN.md §2.12)."""
    from repro.kernels import ops as kernel_ops
    return kernel_ops.kernel_mode()


def _qps(fn, n_queries: int, reps: int = 3) -> float:
    fn()                                    # warm / compile / fill cache
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return n_queries / best


def _throughput(quick: bool) -> None:
    import numpy as np
    from repro.index import builder, corpus as corpus_lib, engine, source
    from repro.index import batch as batch_lib
    from repro.index import pipeline as pipe_lib

    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    n_docs = 1 << 14 if quick else 1 << 16
    n_queries = 32 if quick else 128
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=11, table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    queries = corpus.queries
    batch_sizes = [8, 32] if quick else [8, 32, 128]
    seq_res = [engine.query(idx, q) for q in queries]   # identity oracle

    def assert_identical(out):
        for a, b in zip(out, seq_res):
            assert a.count == b.count and np.array_equal(a.docs, b.docs)

    for regime in ["cached", "uncached"]:
        def make_cache():
            return (engine.DecodeCache(capacity_ints=1 << 26)
                    if regime == "cached" else None)

        seq_cache = make_cache()
        seq_qps = _qps(lambda: [engine.query(idx, q, cache=seq_cache)
                                for q in queries], len(queries))
        emit(f"engine/{regime}/sequential", 1.0 / seq_qps,
             f"{seq_qps:.1f} q/s")
        RESULTS[f"{regime}/sequential_qps"] = round(seq_qps, 1)
        # device-resident index: staged once (untimed — build-time work);
        # one sticky FusionPlan per regime so fused signatures converge
        # across batch sizes and reps (the serving-session contract)
        pool = source.ResidentPool()
        pool.warm(idx)
        plan = batch_lib.FusionPlan()
        for bs in batch_sizes:
            bat_cache = make_cache()

            def run_batched(bs=bs, cache=bat_cache, backend="jax"):
                out = []
                for lo in range(0, len(queries), bs):
                    out.extend(batch_lib.execute_batch(
                        idx, queries[lo: lo + bs], cache=cache, pool=pool,
                        backend=backend, plan=plan))
                return out

            assert_identical(run_batched())
            qps = _qps(run_batched, len(queries))
            emit(f"engine/{regime}/batched_b{bs}", 1.0 / qps,
                 f"{qps:.1f} q/s {qps / seq_qps:.2f}x")
            RESULTS[f"{regime}/batched_b{bs}_qps"] = round(qps, 1)

            def run_pipelined(bs=bs, cache=bat_cache):
                return pipe_lib.execute_pipelined(
                    idx, queries, batch_size=bs, depth=2, cache=cache,
                    pool=pool, plan=plan)

            assert_identical(run_pipelined())
            qps = _qps(run_pipelined, len(queries))
            emit(f"engine/{regime}/pipelined_b{bs}", 1.0 / qps,
                 f"{qps:.1f} q/s {qps / seq_qps:.2f}x")
            RESULTS[f"{regime}/pipelined_b{bs}_qps"] = round(qps, 1)

        # pallas backend coverage in BOTH regimes (pre-ISSUE 3 only the
        # cached regime ever touched the kernels in this table); plain
        # execute_batch so the delta vs batched_b32 isolates the backend
        pal_cache = make_cache()

        def run_pallas():
            out = []
            for lo in range(0, len(queries), 32):
                out.extend(batch_lib.execute_batch(
                    idx, queries[lo: lo + 32], cache=pal_cache, pool=pool,
                    backend="pallas", plan=plan))
            return out

        assert_identical(run_pallas())
        qps = _qps(run_pallas, len(queries))
        emit(f"engine/{regime}/batched_b32_pallas", 1.0 / qps,
             f"{qps:.1f} q/s [{_kernel_mode()}]")
        RESULTS[f"{regime}/batched_b32_pallas_qps"] = round(qps, 1)
        RESULTS[f"{regime}/batched_b32_pallas_kernel_mode"] = _kernel_mode()
        # ISSUE 3 gate: pipelined output byte-identical on the pallas
        # backend too (timed pipelined coverage is the jax column above)
        assert_identical(pipe_lib.execute_pipelined(
            idx, queries, batch_size=32, depth=2, backend="pallas",
            pool=pool, plan=plan))

    # A/B reference: the pre-ISSUE-3 uncached path (per-batch host decode,
    # pow2 padding and H2D staging; no resident pool)
    def run_host_staged():
        out = []
        for lo in range(0, len(queries), 32):
            out.extend(batch_lib.execute_batch(idx, queries[lo: lo + 32]))
        return out

    qps = _qps(run_host_staged, len(queries))
    emit("engine/uncached/batched_b32_host_staged", 1.0 / qps,
         f"{qps:.1f} q/s")
    RESULTS["uncached/batched_b32_host_staged_qps"] = round(qps, 1)


def _dispatch(quick: bool) -> None:
    """Megagroup fusion A/B (ISSUE 5 gates): dispatches per mixed batch
    fused vs unfused (gate: ≥ 4× reduction), AOT warmup compile count, and
    the fused/unfused throughput delta on the device-resident path.
    Identical batches, identical pool — only ``fuse`` flips."""
    import numpy as np
    from repro.index import builder, corpus as corpus_lib, engine, source
    from repro.index import batch as batch_lib

    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    n_docs = 1 << 14 if quick else 1 << 16
    n_queries = 32 if quick else 128
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=11, table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    queries = corpus.queries
    seq = [engine.query(idx, q) for q in queries]
    pool = source.ResidentPool()
    pool.warm(idx)
    plan = batch_lib.FusionPlan()
    wu = batch_lib.warmup(idx, queries, plan=plan, batch_size=32, pool=pool)
    RESULTS["dispatch/warmup_compiles"] = wu["n_compiles"]
    RESULTS["dispatch/warmup_signatures"] = wu["n_signatures"]
    n_batches = (len(queries) + 31) // 32
    for fuse in (False, True):
        label = "fused" if fuse else "unfused"

        def run_once(fuse=fuse, stats=None):
            out = []
            for lo in range(0, len(queries), 32):
                out.extend(batch_lib.execute_batch(
                    idx, queries[lo: lo + 32], pool=pool, fuse=fuse,
                    plan=plan if fuse else None, stats=stats))
            return out

        stats: dict = {}
        out = run_once(stats=stats)
        for a, b in zip(out, seq):              # byte-identical gate
            assert a.count == b.count and np.array_equal(a.docs, b.docs)
        per_batch = stats["n_dispatches"] / n_batches
        RESULTS[f"dispatch/per_batch_{label}"] = round(per_batch, 2)
        qps = _qps(run_once, len(queries))
        RESULTS[f"dispatch/batched_b32_{label}_qps"] = round(qps, 1)
        emit(f"engine/dispatch/batched_b32_{label}", 1.0 / qps,
             f"{qps:.1f} q/s {per_batch:.1f} dispatches/batch")
    RESULTS["dispatch/reduction"] = round(
        RESULTS["dispatch/per_batch_unfused"]
        / max(RESULTS["dispatch/per_batch_fused"], 1e-9), 1)
    # after warmup + the loops above, steady-state fused serving must not
    # compile anything new
    run_stats: dict = {}
    for lo in range(0, len(queries), 32):
        batch_lib.execute_batch(idx, queries[lo: lo + 32], pool=pool,
                                plan=plan, stats=run_stats)
    RESULTS["dispatch/steady_compiles"] = run_stats.get("n_compiles", 0)
    emit("engine/dispatch/reduction", 0.0,
         f"{RESULTS['dispatch/reduction']:.1f}x fewer dispatches, "
         f"{RESULTS['dispatch/steady_compiles']} steady-state compiles")


def _profile(quick: bool) -> None:
    """--profile: per-batch schedule / assemble / dispatch / device-block
    breakdown of the resident fused pipeline, so the next PR can see where
    the next bottleneck sits without re-instrumenting."""
    from repro.index import builder, corpus as corpus_lib, source
    from repro.index import batch as batch_lib
    from repro.index import pipeline as pipe_lib

    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    n_docs = 1 << 14 if quick else 1 << 16
    n_queries = 32 if quick else 128
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=11, table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    queries = corpus.queries
    pool = source.ResidentPool()
    pool.warm(idx)
    plan = batch_lib.FusionPlan()
    batch_lib.warmup(idx, queries, plan=plan, batch_size=32, pool=pool)
    for fuse in (True, False):
        tm = pipe_lib.StageTimings()
        pipe_lib.execute_pipelined(idx, queries, batch_size=32, depth=2,
                                   pool=pool, fuse=fuse,
                                   plan=plan if fuse else None, timings=tm)
        per = 1e3 / max(tm.batches, 1)
        tot = max(tm.stage + tm.assemble + tm.dispatch + tm.block, 1e-9)
        print(f"# profile {'fused' if fuse else 'unfused'} "
              f"(per batch of 32): "
              f"schedule {tm.stage * per:.2f}ms ({tm.stage / tot:.0%}), "
              f"assemble {tm.assemble * per:.2f}ms "
              f"({tm.assemble / tot:.0%}), "
              f"dispatch {tm.dispatch * per:.2f}ms "
              f"({tm.dispatch / tot:.0%}), "
              f"device/block {tm.block * per:.2f}ms ({tm.block / tot:.0%})")


def _skewed(quick: bool) -> None:
    """Decoded-ints/query with the skip path off vs on (ISSUE 2 gate)."""
    from repro.index import builder, corpus as corpus_lib, engine
    from repro.index import batch as batch_lib
    import numpy as np

    # tiny first term, very long second term: the regime where galloping
    # over the block-max index beats decoding (paper §6.5); 1024-int blocks
    # (bp8) give the skip index enough granularity to prune
    n_docs = 1 << 17 if quick else 1 << 18
    n_queries = 8 if quick else 16
    table = {2: (100.0, [0.8 * (1 << 18) / n_docs,
                         38000.0 * (1 << 18) / n_docs])}
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=7, table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="bp8-d1", B=0, n_parts=1)
    queries = corpus.queries
    seq = [engine.query(idx, q) for q in queries]

    decoded = {}
    for skip in (False, True):
        label = "skip_on" if skip else "skip_off"
        stats: dict = {}
        out = batch_lib.execute_batch(idx, queries, skip=skip, stats=stats)
        for a, b in zip(out, seq):              # byte-identical gate
            assert a.count == b.count and np.array_equal(a.docs, b.docs)
        dt = _qps(lambda s=skip: batch_lib.execute_batch(
            idx, queries, skip=s), len(queries))
        per_q = stats["decoded_ints"] / len(queries)
        decoded[label] = per_q
        emit(f"engine/skewed/batched_{label}", 1.0 / dt,
             f"{dt:.1f} q/s {per_q:.0f} decoded ints/q")
        RESULTS[f"skewed/batched_{label}_qps"] = round(dt, 1)
        RESULTS[f"skewed/batched_{label}_decoded_ints_per_query"] = \
            round(per_q)
    ratio = decoded["skip_off"] / max(decoded["skip_on"], 1)
    emit("engine/skewed/partial_decode_ratio", 0.0, f"{ratio:.1f}x fewer")
    RESULTS["skewed/partial_decode_ratio"] = round(ratio, 1)

    # pallas backend: identical results, decoded inside the fused
    # decode+intersect megakernel (DESIGN.md §2.12)
    outp = batch_lib.execute_batch(idx, queries, backend="pallas")
    for a, b in zip(outp, seq):
        assert a.count == b.count and np.array_equal(a.docs, b.docs)
    dt = _qps(lambda: batch_lib.execute_batch(
        idx, queries, backend="pallas"), len(queries))
    emit("engine/skewed/batched_pallas", 1.0 / dt,
         f"{dt:.1f} q/s [{_kernel_mode()}]")
    RESULTS["skewed/batched_pallas_qps"] = round(dt, 1)
    RESULTS["skewed/batched_pallas_kernel_mode"] = _kernel_mode()
    # same-run jax-over-pallas throughput ratio on this fused packed
    # family — the --max-pallas-ratio gate key (1.0 = parity, lower =
    # pallas wins); hard-gated only in compiled mode
    RESULTS["skewed/pallas_vs_jax_ratio"] = round(
        RESULTS["skewed/batched_skip_on_qps"] / max(dt, 1e-9), 2)


def _sharded(quick: bool) -> None:
    """Sharded fan-out scaling (gate: >1.5× batched throughput at
    4 shards vs 1, uncached): batched uncached throughput at shards ∈
    {1, 2, 4} over the devices this process has.  Runs in-process — a
    child that needs the devices would find them held by this process.
    CI sets ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before
    launch so four host-platform devices exist; with fewer devices the
    shards fold onto them (``shard.shard_index``)."""
    import jax
    import numpy as np
    from repro.index import batch as batch_lib
    from repro.index import builder, corpus as corpus_lib, engine, shard

    # device-compute-heavy regime (mid-size seed, two long lists → large
    # candidate-block partial decodes): the regime where the fan-out's
    # SPMD row-split pays; host-bound regimes measure the scheduler, not
    # the sharding
    n_docs = 1 << 17 if quick else 1 << 18
    n_queries = 32 if quick else 96
    scale = n_docs / (1 << 18)
    table = {4: (100.0, [4000.0 * scale, 60000.0 * scale,
                         90000.0 * scale, 130000.0 * scale])}
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=11, table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="bp-d1", B=0, n_parts=4)
    queries = corpus.queries
    seq = [engine.query(idx, q) for q in queries]
    RESULTS["sharded/devices"] = len(jax.devices())
    for n_shards in (1, 2, 4):
        sharded = shard.shard_index(idx, n_shards)

        def run_once():
            return shard.execute_sharded(sharded, queries, batch_size=32,
                                         depth=2)

        out = run_once()
        for a, b in zip(out, seq):              # byte-identical gate
            assert a.count == b.count and np.array_equal(a.docs, b.docs)
        # warm to the signature fixed point before timing: arena growth
        # and residency staging settle over the first passes
        batch_lib.warm_to_fixed_point(
            lambda s: shard.execute_sharded(sharded, queries, batch_size=32,
                                            depth=2, stats=s))
        qps = _qps(run_once, len(queries), reps=5)
        RESULTS[f"sharded/batched_b32_s{n_shards}_qps"] = round(qps, 1)
        emit(f"engine/sharded/batched_b32_s{n_shards}", 1.0 / qps,
             f"{qps:.1f} q/s "
             f"{qps / RESULTS['sharded/batched_b32_s1_qps']:.2f}x")
    RESULTS["sharded/speedup_s4"] = round(
        RESULTS["sharded/batched_b32_s4_qps"]
        / RESULTS["sharded/batched_b32_s1_qps"], 2)
    emit("engine/sharded/speedup_s4", 0.0,
         f"{RESULTS['sharded/speedup_s4']:.2f}x on "
         f"{RESULTS['sharded/devices']} devices")


def _latency(quick: bool) -> None:
    """Open-loop serving latency (ISSUE 6): the continuous-batching server
    under Poisson / bursty arrivals at offered loads derived from this
    run's measured drain capacity.  The drain run is also the acceptance
    check: warmed steady state compiles nothing and serves byte-identical
    results."""
    import numpy as np
    from repro.index import builder, corpus as corpus_lib, engine, source
    from repro.index import batch as batch_lib
    from repro.launch import server as server_lib

    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    n_docs = 1 << 14 if quick else 1 << 16
    n_queries = 64 if quick else 256
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=11, table=table)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    queries = corpus.queries
    seq = [engine.query(idx, q) for q in queries]
    pool = source.ResidentPool()
    pool.warm(idx)
    plan = batch_lib.FusionPlan()

    # drain run: measures capacity AND gates the steady-state claims
    results, srv = server_lib.serve_open_loop(
        idx, queries, qps=0.0, warmup=True, pool=pool, plan=plan,
        max_batch=32, max_queue=max(n_queries, 64))
    for a, b in zip(results, seq):              # byte-identical gate
        assert a.count == b.count and np.array_equal(a.docs, b.docs)
    s = srv.metrics.summary()
    drain_qps = s["qps"]
    RESULTS["latency/drain_qps"] = round(drain_qps, 1)
    RESULTS["latency/steady_compiles"] = srv.stats.get("n_compiles", 0)
    RESULTS["latency/warmup_converged"] = int(srv.warm_report["converged"])
    emit("engine/latency/drain", 1.0 / max(drain_qps, 1e-9),
         f"{drain_qps:.1f} q/s {RESULTS['latency/steady_compiles']} "
         f"steady-state compiles")

    for pattern in ("poisson", "bursty"):
        for frac, tag in ((0.5, "50"), (0.8, "80")):
            offered = max(drain_qps * frac, 1.0)
            out, srv = server_lib.serve_open_loop(
                idx, queries, qps=offered, pattern=pattern, seed=17,
                pool=pool, plan=plan, max_batch=32, max_wait_ms=2.0,
                max_queue=max(n_queries, 64))
            s = srv.metrics.summary()
            key = f"latency/{pattern}{tag}"
            RESULTS[f"{key}_p50_ms"] = round(s["p50_ms"], 2)
            RESULTS[f"{key}_p99_ms"] = round(s["p99_ms"], 2)
            RESULTS[f"{key}_p999_ms"] = round(s["p999_ms"], 2)
            RESULTS[f"{key}_wait_p99_ms"] = round(s["wait_p99_ms"], 2)
            RESULTS[f"{key}_shed"] = s["n_shed"]
            RESULTS[f"{key}_queue_depth_max"] = max(
                (int(k) for k, v in s["queue_depth_hist"].items() if v),
                default=0)
            emit(f"engine/{key}", s["p99_ms"] * 1e-3,
                 f"{s['qps']:.1f} q/s @{offered:.0f} offered, p50 "
                 f"{s['p50_ms']:.1f} / p99 {s['p99_ms']:.1f} / p99.9 "
                 f"{s['p999_ms']:.1f} ms, {s['n_shed']} shed")
    # the --max-p99-ms gate key: Poisson at half capacity (see docstring)
    RESULTS["latency/p99_ms"] = RESULTS["latency/poisson50_p99_ms"]


def _mutation(quick: bool) -> None:
    """Live-mutation serving (ISSUE 9): the segmented mutable index
    (DESIGN.md §2.14) after a burst of adds/seals/deletes, measured in a
    steady state ("frozen" — no merge running) and then *during* a
    background merge, same serving path and batch size — the ratio is the
    serving cost of compaction, which the generation design keeps near
    1.0 (merges stage off-lock and swap one reference).  Both windows are
    gated byte-identical against a rebuild-from-scratch index, and the
    post-swap batches must compile nothing (the candidate generation
    pre-warms through the shared sticky plan)."""
    import numpy as np
    from repro.index import builder, corpus as corpus_lib, engine, segments
    from repro.index import batch as batch_lib

    table = {k: corpus_lib.TABLE2_CLUEWEB[k] for k in (2, 3, 4, 5)}
    n_docs = 1 << 14 if quick else 1 << 16
    n_queries = 32 if quick else 128
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=11, table=table)
    mi = segments.MutableIndex.from_postings(
        corpus.postings, corpus.n_docs, codec_name="fastpfor-d1", B=16,
        n_parts=2)
    queries = corpus.queries
    rng = np.random.default_rng(5)
    term_pool = sorted({t for q in queries for t in q})
    n_mut = 200 if quick else 1000
    for i in range(n_mut):
        k = int(rng.integers(1, 4))
        mi.add(sorted(rng.choice(term_pool, size=k,
                                 replace=False).tolist()))
        if i == n_mut // 2:
            mi.seal()
    for d in rng.choice(mi.next_doc_id, size=n_mut // 10, replace=False):
        mi.delete(int(d))

    def run_once(stats=None):
        out = []
        for lo in range(0, len(queries), 32):
            out.extend(mi.execute_batch(queries[lo: lo + 32],
                                        stats=stats))
        return out

    def assert_identical(out):
        idx = builder.build(mi.live_postings(), max(mi.next_doc_id, 1),
                            codec_name="fastpfor-d1", B=16, n_parts=2)
        for q, a in zip(queries, out):
            b = engine.query(idx, q)
            assert a.count == b.count and np.array_equal(a.docs, b.docs)

    batch_lib.warm_to_fixed_point(lambda s: run_once(stats=s))
    assert_identical(run_once())
    qps_frozen = _qps(run_once, len(queries))
    emit("engine/mutation/frozen", 1.0 / qps_frozen,
         f"{qps_frozen:.1f} q/s ({mi.counters()['n_segments']} segments, "
         f"{mi.counters()['tombstones']} tombstones)")
    RESULTS["mutation/frozen_qps"] = round(qps_frozen, 1)

    # the timed window runs WHILE the background merge decodes, rebuilds
    # and stages the candidate generation
    merge_thread = mi.merge_async(warm_queries=queries)
    loops, t0 = 0, time.perf_counter()
    while loops == 0 or (merge_thread.is_alive() and loops < 64):
        out = run_once()
        loops += 1
    dt = time.perf_counter() - t0
    merge_thread.join()
    assert mi.counters()["n_merges"] == 1
    qps_merge = loops * len(queries) / dt
    ratio = qps_merge / max(qps_frozen, 1e-9)
    emit("engine/mutation/during_merge", 1.0 / qps_merge,
         f"{qps_merge:.1f} q/s {ratio:.2f}x of frozen over {loops} loops")
    RESULTS["mutation/during_merge_qps"] = round(qps_merge, 1)
    RESULTS["mutation/merge_ratio"] = round(ratio, 2)
    RESULTS["mutation/merge_loops"] = loops

    # post-swap: byte-identical to a fresh rebuild, zero compiles
    stats: dict = {}
    assert_identical(run_once(stats=stats))
    RESULTS["mutation/steady_compiles"] = stats.get("n_compiles", 0)
    emit("engine/mutation/post_merge", 0.0,
         f"generation {mi.generation}, "
         f"{RESULTS['mutation/steady_compiles']} post-swap compiles")


def _compression(quick: bool) -> None:
    """Storage autotuner A/B (ISSUE 8): the ``codec_name="auto"`` build vs
    the all-bitpack reference (``bp-d1`` with the varint tail rule off) on
    a Table-2-shaped corpus, whose skewed query-log list lengths leave most
    lists short.  Reports bytes/int and per-codec list counts for both
    builds, asserts the autotuned index byte-identical to the reference on
    both backends, and measures the short-list (< 1024 ints) decode wall
    clock per build — the dispatch-cost term the autotuner's CostModel
    scores on (DESIGN.md §2.13).  ``compression/auto_bytes_per_int`` is
    the ``--max-bytes-per-int`` gate key."""
    import time

    import jax
    import numpy as np
    from repro.core import codecs as codec_lib
    from repro.index import builder, corpus as corpus_lib
    from repro.index import batch as batch_lib

    n_docs = 1 << 15 if quick else 1 << 16
    n_queries = 24 if quick else 40
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=3)
    builds = {
        "auto": builder.build(corpus.postings, corpus.n_docs,
                              codec_name="auto", B=16, n_parts=2),
        "bp": builder.build(corpus.postings, corpus.n_docs,
                            codec_name="bp-d1", B=16, n_parts=2,
                            varint_tail_below=0),
    }
    queries = corpus.queries
    oracle = None
    for label, idx in builds.items():
        st = idx.stats()
        counts = " ".join(f"{k}:{v}" for k, v in
                          sorted(st["codec_counts"].items()))
        emit(f"engine/compression/{label}_bytes_per_int", 0.0,
             f"{st['bytes_per_int']:.2f} B/int [{counts}]")
        RESULTS[f"compression/{label}_bytes_per_int"] = round(
            st["bytes_per_int"], 3)
        for fam, cnt in sorted(st["codec_counts"].items()):
            RESULTS[f"compression/{label}_lists_{fam}"] = cnt
        for backend in ("jax", "pallas"):
            out = batch_lib.execute_batch(idx, queries, backend=backend)
            if oracle is None:
                oracle = out                      # the reference build's
            for a, b in zip(out, oracle):         # results, jax backend
                assert a.count == b.count and np.array_equal(a.docs, b.docs)
        dt = _qps(lambda idx=idx: batch_lib.execute_batch(idx, queries),
                  len(queries))
        emit(f"engine/compression/{label}_batched", 1.0 / dt,
             f"{dt:.1f} q/s")
        RESULTS[f"compression/{label}_qps"] = round(dt, 1)
        # short-list decode wall clock: every "list" payload under 1024
        # ints, decoded through the per-payload registry — the term the
        # autotuner's dispatch-cost model targets
        shorts = [tp.payload for part in idx.parts
                  for tp in part.terms.values()
                  if tp.kind == "list" and tp.n < 1024]
        def decode_all(shorts=shorts):
            for p in shorts:
                jax.block_until_ready(codec_lib.codec_for(p).decode(p))
        decode_all()                              # warm jit caches
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            decode_all()
            best = min(best, time.perf_counter() - t0)
        us = best * 1e6 / max(len(shorts), 1)
        emit(f"engine/compression/{label}_short_decode", us * 1e-6,
             f"{us:.0f} us/list over {len(shorts)} short lists")
        RESULTS[f"compression/{label}_short_decode_us"] = round(us, 1)
    win = (RESULTS["compression/bp_short_decode_us"]
           / max(RESULTS["compression/auto_short_decode_us"], 1e-9))
    emit("engine/compression/short_decode_win", 0.0, f"{win:.1f}x")
    RESULTS["compression/short_decode_win"] = round(win, 2)


def _resilience(quick: bool) -> None:
    """Fault-injected serving + recovery wall clock (DESIGN.md §2.15).

    One open-loop Poisson window clean, one with injected transient
    faults on the first three launches: the faulted window must lose
    ZERO requests
    (every submission resolves ``done``) and answer byte-identically —
    the q/s and p99 deltas are the measured cost of the bounded-backoff
    retry path.  Then a WAL-journaled mutable index takes a mutation
    burst and is recovered from disk, timing the snapshot-load + WAL
    replay path that a post-crash restart pays."""
    import tempfile

    import numpy as np
    from repro.index import builder, corpus as corpus_lib, segments
    from repro.launch import faults as faults_lib
    from repro.launch import server as server_lib

    n_docs = 1 << 14 if quick else 1 << 16
    n_queries = 64 if quick else 256
    corpus = corpus_lib.synthesize(n_docs=n_docs, n_queries=n_queries,
                                   seed=17)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)

    def window(injector=None):
        results, srv = server_lib.serve_open_loop(
            idx, corpus.queries, qps=2000.0, pattern="poisson", seed=2,
            warmup=True, max_batch=8, max_queue=4096, injector=injector,
            max_retries=6, retry_backoff_ms=0.5)
        assert srv.outcomes() == ["done"] * n_queries   # zero lost requests
        return results, srv.metrics.summary()

    clean, s_clean = window()
    # counted rule, not probabilistic: the smoke window only flushes a
    # handful of batches, so a 1%-per-launch rule would usually fire
    # zero times and the "faulted" figures would measure nothing
    inj = faults_lib.FaultInjector("transient@launch:3", seed=12)
    faulted, s_fault = window(injector=inj)
    for a, b in zip(clean, faulted):                    # byte-identical
        assert a.count == b.count and np.array_equal(a.docs, b.docs)
    RESULTS["resilience/clean_qps"] = round(s_clean["qps"], 1)
    RESULTS["resilience/clean_p99_ms"] = round(s_clean["p99_ms"], 2)
    RESULTS["resilience/faulted_qps"] = round(s_fault["qps"], 1)
    RESULTS["resilience/faulted_p99_ms"] = round(s_fault["p99_ms"], 2)
    RESULTS["resilience/faults"] = s_fault["n_faults"]
    RESULTS["resilience/retries"] = s_fault["n_retries"]
    emit("engine/resilience/clean", 1.0 / max(s_clean["qps"], 1e-9),
         f"{s_clean['qps']:.1f} q/s p99 {s_clean['p99_ms']:.2f} ms")
    emit("engine/resilience/faulted", 1.0 / max(s_fault["qps"], 1e-9),
         f"{s_fault['qps']:.1f} q/s p99 {s_fault['p99_ms']:.2f} ms "
         f"({s_fault['n_faults']} faults, {s_fault['n_retries']} retries, "
         f"0 lost)")

    # recovery wall clock: snapshot load + WAL-tail replay after a burst
    rng = np.random.default_rng(9)
    term_pool = sorted({t for q in corpus.queries for t in q})
    n_mut = 200 if quick else 1000
    with tempfile.TemporaryDirectory() as wal_dir:
        from repro.index import durability
        mi = segments.MutableIndex.from_postings(
            corpus.postings, corpus.n_docs, codec_name="fastpfor-d1",
            B=16, n_parts=2, wal=durability.DurableLog(wal_dir))
        for i in range(n_mut):
            k = int(rng.integers(1, 4))
            mi.add(sorted(rng.choice(term_pool, size=k,
                                     replace=False).tolist()))
            if i == n_mut // 2:
                mi.seal()
        for d in rng.choice(mi.next_doc_id, size=n_mut // 10,
                            replace=False):
            mi.delete(int(d))
        t0 = time.perf_counter()
        rec = segments.MutableIndex.recover(wal_dir)
        dt = time.perf_counter() - t0
        live = mi.execute_batch(corpus.queries)
        back = rec.execute_batch(corpus.queries)
        for a, b in zip(live, back):                    # byte-identical
            assert a.count == b.count and np.array_equal(a.docs, b.docs)
        RESULTS["resilience/recovery_s"] = round(dt, 3)
        RESULTS["resilience/recovery_replayed"] = rec._wal_replayed
        emit("engine/resilience/recovery", dt,
             f"{dt * 1e3:.0f} ms to recover ({rec._wal_replayed} WAL "
             f"records replayed, {rec.counters()['n_segments']} segments)")


def run(quick: bool = False) -> None:
    _throughput(quick)
    _dispatch(quick)
    _skewed(quick)
    _compression(quick)
    _sharded(quick)
    _latency(quick)
    _mutation(quick)
    _resilience(quick)


def _mode_mismatch(key: str, bres: dict) -> bool:
    """True when ``key`` is a Pallas entry whose kernel_mode differs between
    baseline and this run — such pairs must never be ratio-gated (an
    interpret number measures the interpreter, not the engine)."""
    mk = key + "_kernel_mode"
    if mk not in bres and mk not in RESULTS:
        return False
    return bres.get(mk) != RESULTS.get(mk)


def compare(baseline_path: str, max_regress: float | None) -> int:
    """Print per-key deltas vs a committed baseline; with ``max_regress``
    also gate on the b32 batched-over-sequential speedup (see module
    docstring for why the gate is a same-run ratio).  Pallas keys carry a
    ``_kernel_mode`` sibling: when it differs between baseline and run the
    delta is printed as NOT COMPARABLE and any gate over such a key is
    refused rather than evaluated across modes."""
    with open(baseline_path) as fh:
        base = json.load(fh)
    bres = base.get("results", {})
    print(f"# compare vs {baseline_path} (baseline quick={base.get('quick')})")
    for key in sorted(set(bres) | set(RESULTS)):
        old, new = bres.get(key), RESULTS.get(key)
        if old is None:
            print(f"#   {key}: (new key) {new}")
        elif new is None:
            print(f"#   {key}: (missing in this run) baseline {old}")
        elif isinstance(old, str) or isinstance(new, str):
            tag = "" if old == new else "  (MODE CHANGED)"
            print(f"#   {key}: {old} -> {new}{tag}")
        elif _mode_mismatch(key, bres):
            print(f"#   {key}: {old} -> {new} "
                  f"(kernel-mode changed: NOT COMPARABLE)")
        else:
            pct = (new - old) / old * 100 if old else float("inf")
            print(f"#   {key}: {old} -> {new} ({pct:+.1f}%)")
    if max_regress is None:
        return 0
    if _mode_mismatch(GATE_NUM, bres) or _mode_mismatch(GATE_DEN, bres):
        print(f"# GATE REFUSED: {GATE_NUM}/{GATE_DEN} kernel mode differs "
              f"from the baseline — interpret vs compiled Pallas numbers "
              f"cannot be ratio-gated; regenerate the baseline in the "
              f"current mode")
        return 2
    try:
        new_ratio = RESULTS[GATE_NUM] / RESULTS[GATE_DEN]
        old_ratio = bres[GATE_NUM] / bres[GATE_DEN]
    except (KeyError, ZeroDivisionError) as exc:
        print(f"# GATE ERROR: missing gate keys ({exc})")
        return 2
    regress = (1.0 - new_ratio / old_ratio) * 100
    print(f"# gate {GATE_NUM}/{GATE_DEN}: baseline {old_ratio:.2f}x, "
          f"now {new_ratio:.2f}x "
          f"({regress:+.1f}% regression; fails above {max_regress:.0f}%)")
    if regress > max_regress:
        print("# GATE FAILED")
        return 2
    print("# gate passed")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep (CI smoke gate)")
    ap.add_argument("--json", type=str, default=None,
                    help="also write the measured baseline to this path")
    ap.add_argument("--compare", type=str, default=None, metavar="PATH",
                    help="print per-key deltas vs a committed baseline JSON")
    ap.add_argument("--max-regress", type=float, default=None, metavar="PCT",
                    help="with --compare: fail (exit 2) if the b32 batched "
                         "speedup regressed more than PCT percent")
    ap.add_argument("--max-dispatches", type=float, default=None,
                    metavar="N",
                    help="fail (exit 2) if the fused engine issues more "
                         "than N device dispatches per mixed batch "
                         "(dispatch/per_batch_fused) — guards against a "
                         "regression back to per-signature dispatch")
    ap.add_argument("--max-pallas-ratio", type=float, default=None,
                    metavar="R",
                    help="fail (exit 2) if the jax backend is more than R "
                         "times faster than the pallas backend on the "
                         "fused packed family (skewed/pallas_vs_jax_ratio, "
                         "a same-run ratio) — ENFORCED only when the "
                         "kernels ran compiled; in interpret mode the "
                         "check is advisory (printed, never failing), "
                         "because interpret timings measure the Pallas "
                         "interpreter, not the engine")
    ap.add_argument("--max-bytes-per-int", type=float, default=None,
                    metavar="B",
                    help="fail (exit 2) if the autotuned build stores more "
                         "than B bytes per posting int "
                         "(compression/auto_bytes_per_int) — guards the "
                         "storage autotuner's compression win")
    ap.add_argument("--max-p99-ms", type=float, default=None, metavar="MS",
                    help="fail (exit 2) if open-loop p99 latency at half "
                         "the measured drain capacity (latency/p99_ms) "
                         "exceeds MS milliseconds — the JSON artifact is "
                         "still written on failure")
    ap.add_argument("--profile", action="store_true",
                    help="print the per-batch schedule/assemble/dispatch/"
                         "device breakdown of the fused resident pipeline "
                         "and exit")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.profile:
        _profile(args.smoke)
        return
    print("name,us_per_call,derived")
    run(quick=args.smoke)
    # evaluate the dispatch gate but keep going: the JSON artifact and the
    # --compare report must land even on a failure — they are exactly the
    # data needed to debug it
    rc = 0
    if args.max_dispatches is not None:
        per_batch = RESULTS.get("dispatch/per_batch_fused")
        if per_batch is None or per_batch > args.max_dispatches:
            print(f"# DISPATCH GATE FAILED: {per_batch} fused dispatches "
                  f"per batch (ceiling {args.max_dispatches})")
            rc = 2
        else:
            print(f"# dispatch gate passed: {per_batch} per batch "
                  f"(ceiling {args.max_dispatches})")
    if args.max_pallas_ratio is not None:
        ratio = RESULTS.get(PALLAS_GATE)
        kmode = RESULTS.get(PALLAS_GATE_MODE, "interpret")
        if kmode != "compiled":
            print(f"# pallas ratio gate ADVISORY (kernel_mode={kmode}): "
                  f"jax/pallas = {ratio}x, target <= "
                  f"{args.max_pallas_ratio}x — interpret-mode numbers are "
                  f"never hard-gated; the gate enforces once the kernels "
                  f"run compiled")
        elif ratio is None or ratio > args.max_pallas_ratio:
            print(f"# PALLAS RATIO GATE FAILED: jax/pallas = {ratio}x on "
                  f"the fused packed family (ceiling "
                  f"{args.max_pallas_ratio}x, compiled mode)")
            rc = 2
        else:
            print(f"# pallas ratio gate passed: jax/pallas = {ratio}x "
                  f"(ceiling {args.max_pallas_ratio}x, compiled mode)")
    if args.max_bytes_per_int is not None:
        bpi = RESULTS.get("compression/auto_bytes_per_int")
        ref = RESULTS.get("compression/bp_bytes_per_int")
        if bpi is None or bpi > args.max_bytes_per_int:
            print(f"# BYTES/INT GATE FAILED: autotuned build stores {bpi} "
                  f"B/int (ceiling {args.max_bytes_per_int}; all-bitpack "
                  f"reference {ref})")
            rc = 2
        else:
            print(f"# bytes/int gate passed: autotuned {bpi} B/int "
                  f"(ceiling {args.max_bytes_per_int}; all-bitpack "
                  f"reference {ref})")
    if args.max_p99_ms is not None:
        p99 = RESULTS.get("latency/p99_ms")
        if p99 is None or p99 > args.max_p99_ms:
            print(f"# P99 GATE FAILED: {p99} ms open-loop p99 at half "
                  f"capacity (ceiling {args.max_p99_ms} ms)")
            rc = 2
        else:
            print(f"# p99 gate passed: {p99} ms (ceiling "
                  f"{args.max_p99_ms} ms)")
    if args.json:
        payload = {
            "bench": "bench_engine",
            "quick": bool(args.smoke),
            "results": RESULTS,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {args.json}")
    if args.compare:
        rc = max(rc, compare(args.compare, args.max_regress))
    if rc:
        sys.exit(rc)


if __name__ == "__main__":
    main()
