"""Serving launcher.

  --arch paper-index : conjunctive query serving (the paper's system);
                       --batch N > 1 routes through the shape-bucketed
                       batched scheduler (repro.index.batch), --backend
                       {jax,pallas} picks the intersect backend,
                       --resident stages the device-resident index
                       (source.ResidentPool), --pipeline D double-buffers
                       batches at depth D with a per-stage timing breakdown
                       (stage/assemble/dispatch/block;
                       repro.index.pipeline), --fuse (default) collapses
                       each batch to O(1) fused megagroup programs
                       (--no-fuse for the per-signature A/B), --warmup
                       precompiles the fused family ladder before the
                       timed run (AOT signature warmup, DESIGN.md §2.10)
  --arch <lm id>     : prefill + greedy decode on the smoke-reduced model
  --arch <recsys id> : batched scoring

  PYTHONPATH=src python -m repro.launch.serve --arch paper-index --queries 20
  PYTHONPATH=src python -m repro.launch.serve --arch paper-index \\
      --queries 256 --batch 64 --backend jax --cache --shared-vocab
  PYTHONPATH=src python -m repro.launch.serve --arch paper-index \\
      --queries 256 --batch 32 --pipeline 2
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import get_config


def coerce_index_flags(args) -> list[str]:
    """Normalise paper-index flag interactions, returning one warning line
    per coerced or ignored flag.

    Earlier revisions rewrote flags silently (``--shards`` turned
    ``--batch 1`` into 32 and dropped ``--cache`` with only a partial
    note), so a user could not tell the run they asked for from the run
    they got.  Every implied rewrite is now explicit; ``args`` is mutated
    in place so the serving paths read the *effective* values."""
    warnings = []
    # durability / chaos / live-traffic flags (DESIGN.md §2.15) — resolved
    # first because --wal can imply --mutate, which the branches below read
    if getattr(args, "wal", None) and not getattr(args, "mutate", 0):
        warnings.append("--wal implies the mutable index: --mutate 0 -> 256")
        args.mutate = 256
    if getattr(args, "chaos", None) and not getattr(args, "wal", None):
        warnings.append("--chaos without --wal: durability crash points "
                        "(wal.*/snapshot.*/merge.*) have no durable "
                        "directory to recover from — only launch/collect "
                        "seam faults can fire safely")
    if (getattr(args, "timeout_ms", None) is not None
            and not getattr(args, "qps", 0)):
        warnings.append("--timeout-ms ignored without --qps (offline and "
                        "drain serving have no per-request deadlines)")
        args.timeout_ms = None
    if getattr(args, "qps", 0):
        if args.pipeline:
            warnings.append("--pipeline ignored with --qps (the live "
                            "server bounds in-flight batches itself)")
            args.pipeline = 0
        if args.shards:
            warnings.append("--shards ignored with --qps (use "
                            "repro.launch.server --shards for live "
                            "sharded serving)")
            args.shards = 0
        if args.batch <= 1:
            warnings.append(f"--qps implies batched mode: "
                            f"--batch {args.batch} -> 32")
            args.batch = 32
    if getattr(args, "mutate", 0):
        if args.batch <= 1:
            warnings.append(f"--mutate implies batched mode: "
                            f"--batch {args.batch} -> 32")
            args.batch = 32
        if args.pipeline:
            warnings.append("--pipeline ignored with --mutate (the mutable "
                            "path batches against generation snapshots)")
            args.pipeline = 0
        if args.cache:
            warnings.append("--cache ignored with --mutate (decoded "
                            "results change as the corpus mutates)")
            args.cache = False
        if not args.resident:
            warnings.append("--mutate implies the device-resident index: "
                            "--resident on (each generation owns a warmed "
                            "ResidentPool)")
            args.resident = True
        return warnings
    if getattr(args, "delete_frac", None) is not None:
        warnings.append("--delete-frac ignored without --mutate")
        args.delete_frac = None
    if args.shards:
        if args.batch <= 1:
            warnings.append(f"--shards implies batched mode: "
                            f"--batch {args.batch} -> 32")
            args.batch = 32
        if not args.pipeline:
            warnings.append("--shards implies pipelined serving: "
                            "--pipeline 0 -> 2")
            args.pipeline = 2
        if args.cache:
            warnings.append("--cache ignored with --shards (per-shard "
                            "device residency supersedes the decode cache)")
            args.cache = False
        if not args.resident:
            warnings.append("--shards implies the device-resident index: "
                            "--resident on")
            args.resident = True
    elif args.pipeline:
        if args.batch <= 1:
            warnings.append(f"--pipeline implies batched mode: "
                            f"--batch {args.batch} -> 32")
            args.batch = 32
        if not args.resident:
            warnings.append("--pipeline implies the device-resident index: "
                            "--resident on")
            args.resident = True
    if args.warmup and not args.fuse:
        warnings.append("--warmup warms the fused family ladder; with "
                        "--no-fuse the signature fixed-point loop covers it")
    return warnings


# --codec flag value -> builder codec name ("auto" goes to the storage
# autotuner; everything else pins one family index-wide)
_CODEC_NAMES = {"auto": "auto", "bitpack": "bp-d1",
                "streamvbyte": "streamvbyte-d1", "composite": "composite-d1",
                "fastpfor": "fastpfor-d1", "varint": "varint"}


def _codec_name(args) -> str:
    return _CODEC_NAMES[getattr(args, "codec", "fastpfor")]


def _print_codec_stats(args, idx) -> None:
    """Storage report next to the build: bytes/int plus how many lists
    landed in each codec family (the autotuner's visible output)."""
    st = idx.stats()
    counts = " ".join(f"{k}:{v}" for k, v in
                      sorted(st["codec_counts"].items()))
    print(f"[serve] index codec {getattr(args, 'codec', 'fastpfor')}: "
          f"{st['bytes_per_int']:.2f} bytes/int "
          f"({st['bits_per_int']:.2f} bits/int) [{counts}]")


def serve_index(args):
    from repro.index import builder, corpus as corpus_lib, engine, source
    for w in coerce_index_flags(args):
        print(f"[serve] warning: {w}")
    from repro.kernels import ops as kernel_ops
    kmode = kernel_ops.set_kernel_mode(getattr(args, "kernel_mode", "auto"))
    if args.backend == "pallas":
        print(f"[serve] pallas kernel mode: {kmode}"
              + (" (interpret — timings not comparable to compiled; "
                 "see DESIGN.md §2.12)" if kmode == "interpret" else ""))
    corpus = corpus_lib.synthesize(n_docs=1 << 16, n_queries=args.queries,
                                   seed=5, shared_vocab=args.shared_vocab)
    if getattr(args, "qps", 0):
        return serve_index_live(args, corpus)
    if getattr(args, "mutate", 0):
        return serve_index_mutable(args, corpus)
    if args.shards:
        return serve_index_sharded(args, corpus)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name=_codec_name(args), B=16, n_parts=2)
    _print_codec_stats(args, idx)
    queries = corpus.queries
    cache = engine.DecodeCache() if args.cache else None
    pool = None
    if args.resident or args.pipeline:
        pool = source.ResidentPool()
        t0 = time.perf_counter()
        pool.warm(idx)
        ps = pool.stats()
        print(f"[serve] resident index: staged {ps['staged_lists']} lists "
              f"({ps['staged_ints']} ints) in {time.perf_counter() - t0:.2f}s")

    def cache_note():
        note = ""
        if cache is not None:
            note += f", cache hit rate {cache.hit_rate:.2f}"
        if pool is not None:
            ps = pool.stats()
            note += (f", pool {ps['resident_lists']} lists resident "
                     f"({ps['evicted_lists']} evicted)")
        return note

    if args.batch > 1:
        from repro.index import batch as batch_lib
        from repro.index import pipeline as pipe_lib

        depth = args.pipeline
        plan = batch_lib.FusionPlan() if args.fuse else None

        def run_all(stats=None, timings=None):
            stats = {} if stats is None else stats
            if depth:
                out = pipe_lib.execute_pipelined(
                    idx, queries, batch_size=args.batch, depth=depth,
                    backend=args.backend, cache=cache, pool=pool,
                    fuse=args.fuse, plan=plan, stats=stats,
                    timings=timings)
            else:
                out = []
                for lo in range(0, len(queries), args.batch):
                    out.extend(batch_lib.execute_batch(
                        idx, queries[lo: lo + args.batch],
                        backend=args.backend, cache=cache, pool=pool,
                        fuse=args.fuse, plan=plan, stats=stats))
            return out, stats

        if args.warmup and args.fuse:
            # AOT signature warmup: compile the fused family ladder before
            # the first timed batch (DESIGN.md §2.10); the query stream is
            # its own most representative sample
            wu = batch_lib.warmup(idx, queries, plan=plan,
                                  batch_size=args.batch,
                                  backend=args.backend, pool=pool,
                                  cache=cache)
            print(f"[serve] warmup: {wu['n_compiles']} compiles over "
                  f"{wu['n_signatures']} signatures in {wu['passes']} "
                  f"passes ({wu['time_s']:.2f}s)")
            if not wu.get("converged", True):
                print("[serve] warning: warmup stopped at max_passes "
                      "before the signature ladder reached a fixed point "
                      "— steady-state serving may still compile")
        else:
            # Warm to steady state: cache fills / pool staging change how
            # terms resolve between passes (decoded vs packed), which
            # changes group signatures — so repeat until no new program
            # signature appears, otherwise the timed loop pays compile on
            # its first batches.
            n_sigs, passes, converged = batch_lib.warm_to_fixed_point(
                lambda s: run_all(stats=s))
            if not converged:
                print(f"[serve] warning: signature warm loop stopped at "
                      f"max_passes ({passes} passes, {n_sigs} signatures) "
                      f"without converging — the timed run may pay hidden "
                      f"compiles")
        timings = pipe_lib.StageTimings() if depth else None
        t0 = time.perf_counter()
        results, stats = run_all(timings=timings)
        dt = time.perf_counter() - t0
        hits = sum(r.count for r in results)
        mode = (f"--pipeline {depth} (batch {args.batch})" if depth
                else f"--batch {args.batch}")
        n_batches = max((len(queries) + args.batch - 1) // args.batch, 1)
        print(f"[serve] paper-index {mode} ({args.backend}"
              f"{', fused' if args.fuse else ', unfused'}): "
              f"{len(queries)} queries, {len(queries) / dt:.1f} q/s "
              f"({dt / len(queries) * 1e3:.2f} ms/query), {hits} hits, "
              f"{stats.get('n_dispatches', 0)} dispatches "
              f"({stats.get('n_dispatches', 0) / n_batches:.1f}/batch, "
              f"{len(stats.get('signatures', ()))} programs, "
              f"{stats.get('n_compiles', 0)} compiles), "
              f"{stats.get('decoded_ints', 0) / len(queries):.0f} "
              f"decoded ints/query "
              f"({stats.get('skip_folds', 0)} skip folds, "
              f"{stats.get('resident_hits', 0)} resident hits), "
              f"{idx.stats()['bits_per_int']:.2f} bits/int"
              f"{cache_note()}")
        if timings is not None:
            tot = max(timings.stage + timings.assemble + timings.dispatch
                      + timings.block, 1e-9)
            print(f"[serve]   pipeline depth {depth}: "
                  f"stage {timings.stage * 1e3:.1f} ms "
                  f"({timings.stage / tot:.0%}), "
                  f"assemble {timings.assemble * 1e3:.1f} ms "
                  f"({timings.assemble / tot:.0%}), "
                  f"dispatch {timings.dispatch * 1e3:.1f} ms "
                  f"({timings.dispatch / tot:.0%}), "
                  f"block {timings.block * 1e3:.1f} ms "
                  f"({timings.block / tot:.0%}) "
                  f"over {timings.batches} batches")
        return
    # warm / compile every signature; two passes when residency (cache or
    # pool) changes how terms resolve — steady state, not first-touch
    for _ in range(2 if (cache is not None or pool is not None) else 1):
        for q in queries:
            engine.query(idx, q, cache=cache, pool=pool)
    stats: dict = {}
    t0 = time.perf_counter()
    hits = sum(engine.query(idx, q, cache=cache, pool=pool,
                            stats=stats).count
               for q in queries)
    dt = time.perf_counter() - t0
    print(f"[serve] paper-index: {len(queries)} queries, "
          f"{len(queries) / dt:.1f} q/s "
          f"({dt / len(queries) * 1e3:.2f} ms/query), {hits} hits, "
          f"{stats.get('decoded_ints', 0) / len(queries):.0f} "
          f"decoded ints/query ({stats.get('skip_folds', 0)} skip folds), "
          f"{idx.stats()['bits_per_int']:.2f} bits/int"
          f"{cache_note()}")


def _injector(args):
    """Build the chaos FaultInjector from --chaos (None when unarmed)."""
    spec = getattr(args, "chaos", None)
    if not spec:
        return None
    from repro.launch import faults as faults_lib
    return faults_lib.FaultInjector(spec, seed=getattr(args, "seed", 0) or 0)


def _bootstrap_mutable(args, corpus, injector=None):
    """Shared --mutate bootstrap: build the MutableIndex (WAL-backed when
    --wal is set), apply the add/seal/delete stream, and — if an injected
    crash fires mid-mutation — recover from the WAL directory and keep
    going with the recovered state (DESIGN.md §2.15)."""
    from repro.index import segments
    log = None
    if getattr(args, "wal", None):
        from repro.index import durability
        log = durability.DurableLog(args.wal, injector=injector)
    n_mut = args.mutate
    del_frac = 0.1 if args.delete_frac is None else args.delete_frac
    t0 = time.perf_counter()
    mi = segments.MutableIndex.from_postings(
        corpus.postings, corpus.n_docs, codec_name=_codec_name(args),
        B=16, n_parts=2, n_shards=args.shards, wal=log)
    print(f"[serve] mutable index bootstrapped: {corpus.n_docs} docs "
          f"sealed in {time.perf_counter() - t0:.2f}s"
          + (f", {args.shards} shards" if args.shards else "")
          + (f", WAL at {args.wal}" if log is not None else ""))

    queries = corpus.queries
    rng = np.random.default_rng(7)
    term_pool = sorted({t for q in queries for t in q})
    n_del = int(del_frac * n_mut)
    crashed = False
    try:
        for i in range(n_mut):
            k = int(rng.integers(1, 4))
            mi.add(sorted(rng.choice(term_pool, size=k,
                                     replace=False).tolist()))
            if n_mut > 1 and i == n_mut // 2:
                mi.seal()               # live stream: seal mid-mutation
        if n_del:
            for d in rng.choice(mi.next_doc_id, size=n_del, replace=False):
                mi.delete(int(d))
    except Exception as e:              # noqa: BLE001 — chaos crash path
        from repro.launch import faults as faults_lib
        if not isinstance(e, faults_lib.InjectedCrash) or log is None:
            raise
        # the injected "process death": everything not yet applied is
        # lost; recovery replays snapshot + WAL tail and serving resumes
        print(f"[serve] chaos: {e} — recovering from {args.wal}")
        crashed = True
        injector.disarm_all()
        t0 = time.perf_counter()
        mi = segments.MutableIndex.recover(args.wal, injector=injector)
        print(f"[serve] recovered in {time.perf_counter() - t0:.2f}s: "
              f"replayed {mi._wal_replayed} WAL records, "
              f"{mi.counters()['n_segments']} segments, "
              f"{mi.counters()['mutable_docs']} mutable docs")
    c = mi.counters()
    stream = (f"crash cut the +{n_mut}/-{n_del} mutation stream short"
              if crashed else f"+{n_mut} docs / -{n_del} tombstones")
    print(f"[serve] mutable index: {stream} -> "
          f"generation {c['generation']}, {c['n_segments']} sealed "
          f"segments + {c['mutable_docs']} mutable docs, "
          f"{c['tombstones']} tombstones, {c['n_seals']} seals, "
          f"vocab {c['vocab']}")
    return mi, n_del


def _recovery_differential(args, mi, queries):
    """--wal epilogue: recover a second index from the durable directory
    and assert it answers byte-identically to the live one."""
    from repro.index import segments
    t0 = time.perf_counter()
    ri = segments.MutableIndex.recover(args.wal)
    dt = time.perf_counter() - t0
    got = mi.execute_batch(queries, backend=args.backend, fuse=args.fuse)
    rec = ri.execute_batch(queries, backend=args.backend, fuse=args.fuse)
    for q, g, r in zip(queries, got, rec):
        assert g.count == r.count and np.array_equal(g.docs, r.docs), \
            f"recovery mismatch on {q}"
    print(f"[serve] recovery check: replayed {ri._wal_replayed} WAL "
          f"records in {dt:.2f}s; {len(queries)} queries byte-identical "
          f"to the live index")


def serve_index_mutable(args, corpus):
    """--mutate N: live-corpus serving demo over the segmented mutable
    index (DESIGN.md §2.14).

    Bootstraps a MutableIndex from the synthetic corpus, applies N adds
    (with a mid-stream seal) and ``--delete-frac``·N tombstones, warms to
    the signature fixed point, then runs the timed loop *while a
    background merge compacts the sealed segments* — the printed q/s is
    throughput during the merge, and the run ends with a differential
    check against a rebuild-from-scratch index.  With --wal DIR every
    mutation is journaled and the run ends with a crash-recovery
    differential as well (DESIGN.md §2.15)."""
    from repro.index import batch as batch_lib, builder, engine
    injector = _injector(args)
    n_mut = args.mutate
    del_frac = 0.1 if args.delete_frac is None else args.delete_frac
    mi, n_del = _bootstrap_mutable(args, corpus, injector)
    queries = corpus.queries

    def run_all(stats=None):
        stats = {} if stats is None else stats
        out = []
        for lo in range(0, len(queries), args.batch):
            out.extend(mi.execute_batch(queries[lo: lo + args.batch],
                                        backend=args.backend,
                                        fuse=args.fuse, stats=stats))
        return out, stats

    t0 = time.perf_counter()
    c0 = batch_lib._compile_count()
    n_sigs, passes, converged = batch_lib.warm_to_fixed_point(
        lambda s: run_all(stats=s))
    if args.warmup:
        print(f"[serve] warmup: {batch_lib._compile_count() - c0} compiles "
              f"over {n_sigs} signatures in {passes} passes "
              f"({time.perf_counter() - t0:.2f}s)")
    if not converged:
        print("[serve] warning: signature warm loop stopped at max_passes "
              "without converging — the timed run may pay hidden compiles")

    # timed loop under a live background merge: the candidate generation
    # pre-warms through the shared sticky plan before the atomic swap;
    # --chaos merge.* points fire through the stage hook and exercise the
    # merge retry path
    merge_hook = injector.merge_hook() if injector is not None else None
    merge_thread = mi.merge_async(warm_queries=queries,
                                  backend=args.backend, hook=merge_hook)
    stats: dict = {}
    t0 = time.perf_counter()
    loops = 0
    while loops == 0 or (merge_thread.is_alive() and loops < 64):
        results, _ = run_all(stats=stats)
        loops += 1
    dt = time.perf_counter() - t0
    merge_thread.join()
    n_q = loops * len(queries)
    hits = sum(r.count for r in results)
    c = mi.counters()
    print(f"[serve] paper-index --mutate {n_mut} "
          f"--delete-frac {del_frac:g} ({args.backend}"
          f"{', fused' if args.fuse else ', unfused'}, "
          f"batch {args.batch}): {n_q} queries in {loops} loops during "
          f"background merge, {n_q / dt:.1f} q/s "
          f"({dt / n_q * 1e3:.2f} ms/query), {hits} hits, "
          f"{stats.get('n_compiles', 0)} compiles")
    print(f"[serve]   post-merge: generation {c['generation']}, "
          f"{c['n_segments']} segments, {c['n_merges']} merges, "
          f"{c['next_doc_id']} doc ids ({c['tombstones']} tombstoned)")
    if c.get("merge_failures"):
        print(f"[serve]   merge retries: {c['merge_failures']} failed "
              f"attempts, last error: {c['last_merge_error'] or 'cleared'}")

    # differential: the served state vs a rebuild-from-scratch index
    idx = builder.build(mi.live_postings(), max(mi.next_doc_id, 1),
                        codec_name=_codec_name(args), B=16, n_parts=2)
    final, _ = run_all()
    for q, got in zip(queries, final):
        want = engine.query(idx, q)
        assert got.count == want.count and \
            np.array_equal(got.docs, want.docs), f"mismatch on {q}"
    print(f"[serve] differential check: {len(queries)} queries "
          f"byte-identical to rebuild-from-scratch")
    if getattr(args, "wal", None):
        _recovery_differential(args, mi, queries)
    if injector is not None:
        print(f"[serve] chaos: {injector.counts()}")
    return final


def serve_index_live(args, corpus):
    """--qps Q: open-loop live serving through the continuous-batching
    server (repro.launch.server) with the resilience knobs — per-request
    deadlines (--timeout-ms), injected faults (--chaos), and a durable
    mutable corpus (--wal, --mutate).  DESIGN.md §2.11 and §2.15.

    Every submitted request resolves to exactly one of done / shed /
    timeout / error; the epilogue audits that and, for --mutate, runs the
    served-results differential against the live index (plus the WAL
    recovery differential when --wal is set)."""
    from repro.index import batch as batch_lib, builder, source
    from repro.launch import server as server_lib
    injector = _injector(args)
    queries = corpus.queries
    kw = dict(backend=args.backend, max_batch=args.batch, fuse=args.fuse,
              timeout_ms=getattr(args, "timeout_ms", None),
              injector=injector)
    mi = idx = None
    if getattr(args, "mutate", 0):
        mi, _ = _bootstrap_mutable(args, corpus, injector)
        kw["mutable"] = mi
    else:
        idx = builder.build(corpus.postings, corpus.n_docs,
                            codec_name=_codec_name(args), B=16, n_parts=2)
        _print_codec_stats(args, idx)
        if args.resident:
            pool = source.ResidentPool()
            pool.warm(idx)
            kw["pool"] = pool
    results, server = server_lib.serve_open_loop(
        idx, queries, qps=args.qps, warmup=args.warmup,
        seed=getattr(args, "seed", 0) or 0, **kw)
    s = server.metrics.summary()
    outs = server.outcomes()
    assert len(outs) == len(queries) and "pending" not in outs, \
        "unresolved requests after run()"  # the zero-lost-requests audit
    lad = server.ladder
    print(f"[serve] paper-index --qps {args.qps:g} ({args.backend}"
          f"{', fused' if args.fuse else ', unfused'}, "
          f"batch {args.batch}"
          + (f", timeout {args.timeout_ms:g} ms"
             if getattr(args, "timeout_ms", None) is not None else "")
          + f"): {s['n_done']} done / {s['n_shed']} shed / "
          f"{s['n_timeout']} timed out / {s['n_errors']} errored, "
          f"{s['qps']:.1f} q/s, p50 {s['p50_ms']:.2f} ms, "
          f"p99 {s['p99_ms']:.2f} ms")
    print(f"[serve]   resilience: {s['n_faults']} faults, "
          f"{s['n_retries']} retries, {s['degraded_flushes']} degraded "
          f"flushes, {lad.n_degradations} degradations / "
          f"{lad.n_promotions} promotions, final rung "
          f"{lad.current[0]}{'+fused' if lad.current[1] else '+unfused'}")
    if injector is not None:
        print(f"[serve] chaos: {injector.counts()}")
    # differential: every answered request must match a clean re-execution
    # against the same (final) corpus state — degraded or retried flushes
    # included
    served = [(q, r) for q, r in zip(queries, results) if r is not None]
    if served:
        qs = [q for q, _ in served]
        if mi is not None:
            want = mi.execute_batch(qs, backend=args.backend,
                                    fuse=args.fuse)
        else:
            want = batch_lib.execute_batch(idx, qs, backend=args.backend,
                                           fuse=args.fuse)
        for (q, got), w in zip(served, want):
            assert got.count == w.count and \
                np.array_equal(got.docs, w.docs), f"mismatch on {q}"
        print(f"[serve] differential check: {len(served)} answered "
              f"queries byte-identical to direct execution")
    if mi is not None and getattr(args, "wal", None):
        _recovery_differential(args, mi, queries)
    return results


def serve_index_sharded(args, corpus):
    """--shards N: multi-device fan-out serving (repro.index.shard).

    Each index part's working set is pinned to its shard's device; batches
    fan out to all shards in one SPMD dispatch and per-part hits
    concatenate in part order — byte-identical to single-device serving.
    Run under XLA_FLAGS=--xla_force_host_platform_device_count=N to get N
    host-platform devices on CPU-only machines (must be set before jax
    initializes; with fewer devices, shards share them contiguously)."""
    from repro.index import builder, pipeline as pipe_lib, shard as shard_lib
    t0 = time.perf_counter()
    sharded = builder.build_sharded(
        corpus.postings, corpus.n_docs, n_shards=args.shards,
        codec_name=_codec_name(args), B=16,
        n_parts=max(args.shards, 2))
    _print_codec_stats(args, sharded.index)
    st = sharded.stats()
    print(f"[serve] sharded index: {st['n_shards']} shards on "
          f"{st['n_devices']} devices, warmed in "
          f"{time.perf_counter() - t0:.2f}s")
    for s in st["shards"]:
        print(f"[serve]   shard {s['shard']} -> {s['device']}: "
              f"parts {s['parts']}, {s['resident_lists']} lists "
              f"({s['resident_ints']} ints) resident")
    queries = corpus.queries
    batch = args.batch                  # coerce_index_flags normalised these
    depth = args.pipeline
    from repro.index import batch as batch_lib
    plan = batch_lib.FusionPlan() if args.fuse else None

    def run_all(stats=None, timings=None):
        return shard_lib.execute_sharded(
            sharded, queries, batch_size=batch, depth=depth,
            backend=args.backend, fuse=args.fuse, plan=plan,
            stats=stats, timings=timings)

    # warm to signature fixed point (same rationale as the batched path);
    # with --warmup the compile accounting of the pass is reported
    c0 = batch_lib._compile_count()
    t0 = time.perf_counter()
    n_sigs, passes, converged = batch_lib.warm_to_fixed_point(
        lambda s: run_all(stats=s))
    if args.warmup:
        print(f"[serve] warmup: {batch_lib._compile_count() - c0} compiles "
              f"over {n_sigs} signatures in {passes} passes "
              f"({time.perf_counter() - t0:.2f}s)")
    if not converged:
        print(f"[serve] warning: signature warm loop stopped at max_passes "
              f"({passes} passes, {n_sigs} signatures) without converging "
              f"— the timed run may pay hidden compiles")
    timings = pipe_lib.StageTimings()
    stats: dict = {}
    t0 = time.perf_counter()
    results = run_all(stats=stats, timings=timings)
    dt = time.perf_counter() - t0
    hits = sum(r.count for r in results)
    n_batches = max((len(queries) + batch - 1) // batch, 1)
    print(f"[serve] paper-index --shards {args.shards} "
          f"(batch {batch}, depth {depth}, {args.backend}"
          f"{', fused' if args.fuse else ', unfused'}): "
          f"{len(queries)} queries, {len(queries) / dt:.1f} q/s "
          f"({dt / len(queries) * 1e3:.2f} ms/query), {hits} hits, "
          f"{stats.get('n_dispatches', 0)} dispatches "
          f"({stats.get('n_dispatches', 0) / n_batches:.1f}/batch, "
          f"{len(stats.get('signatures', ()))} programs, "
          f"{stats.get('n_compiles', 0)} compiles)")
    tot = max(timings.stage + timings.assemble + timings.dispatch
              + timings.block, 1e-9)
    print(f"[serve]   stage {timings.stage * 1e3:.1f} ms "
          f"({timings.stage / tot:.0%}), "
          f"assemble {timings.assemble * 1e3:.1f} ms "
          f"({timings.assemble / tot:.0%}), "
          f"dispatch {timings.dispatch * 1e3:.1f} ms "
          f"({timings.dispatch / tot:.0%}), "
          f"block {timings.block * 1e3:.1f} ms ({timings.block / tot:.0%})")
    return results


def serve_lm(args, spec):
    from repro.models.transformer import init_params
    from repro.serve.steps import greedy_generate
    cfg = spec.smoke_config()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = args.batch or 4
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, 16),
                                0, cfg.vocab)
    t0 = time.perf_counter()
    out = greedy_generate(params, cfg, prompt, max_new=args.tokens,
                          cache_len=16 + args.tokens)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"[serve] {spec.arch_id}: batch={batch} generated "
          f"{args.tokens} tokens in {dt:.2f}s "
          f"({batch * args.tokens / dt:.1f} tok/s); sample: "
          f"{np.asarray(out[0, :8]).tolist()}")


def serve_recsys(args, spec):
    from repro.data import recsys_data
    from repro.models import recsys
    cfg = spec.smoke_config()
    params = recsys.INIT[cfg.arch](jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    mk = {"din": recsys_data.din_batch, "sasrec": recsys_data.seq_batch,
          "bert4rec": recsys_data.bert4rec_batch,
          "mind": recsys_data.mind_batch}[cfg.arch]
    batch = args.batch or 4
    b = {k: jnp.asarray(v) for k, v in mk(rng, cfg, batch).items()}
    score = jax.jit(lambda p, bb: recsys.SCORE[cfg.arch](p, bb, cfg))
    score(params, b)                        # warm
    t0 = time.perf_counter()
    s = score(params, b)
    jax.block_until_ready(s)
    dt = time.perf_counter() - t0
    print(f"[serve] {spec.arch_id}: scored batch={batch} in "
          f"{dt * 1e3:.2f} ms; mean score {float(s.mean()):.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--queries", type=int, default=20)
    ap.add_argument("--batch", type=int, default=0,
                    help="paper-index: >1 enables batched scheduler; "
                         "lm/recsys: batch size (default 4)")
    ap.add_argument("--backend", choices=["jax", "pallas"], default="jax")
    ap.add_argument("--kernel-mode", choices=["auto", "compiled", "interpret"],
                    default="auto",
                    help="Pallas kernel execution mode: auto probes the "
                         "runtime backend (compiled Mosaic on TPU, "
                         "interpret elsewhere; REPRO_PALLAS_INTERPRET "
                         "overrides); compiled/interpret force it "
                         "(DESIGN.md §2.12)")
    ap.add_argument("--pipeline", type=int, default=0, metavar="DEPTH",
                    help="paper-index: double-buffered pipelined serving "
                         "with DEPTH batches in flight (implies the "
                         "device-resident index and batched mode — batch "
                         "size defaults to 32 unless --batch is given; "
                         "0 = off)")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="paper-index: serve the index sharded across N "
                         "data-parallel device shards (implies batched + "
                         "pipelined + resident; run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N for N "
                         "host devices; 0 = off)")
    ap.add_argument("--resident", action="store_true",
                    help="paper-index: stage the device-resident index "
                         "(source.ResidentPool) before serving")
    ap.add_argument("--fuse", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="paper-index: coarsen each batch's groups into "
                         "megagroup families — O(1) device programs per "
                         "batch (--no-fuse keeps one program per shape "
                         "signature; results are identical)")
    ap.add_argument("--warmup", action="store_true",
                    help="paper-index: AOT signature warmup — precompile "
                         "the fused family ladder before the timed run so "
                         "steady-state serving never compiles")
    ap.add_argument("--codec",
                    choices=["auto", "bitpack", "streamvbyte", "composite",
                             "fastpfor", "varint"],
                    default="fastpfor",
                    help="paper-index: posting-list codec family (auto = "
                         "the cost-model storage autotuner picks codec + "
                         "skip policy per list; DESIGN.md §2.13)")
    ap.add_argument("--mutate", type=int, default=0, metavar="N",
                    help="paper-index: live-corpus demo — apply N adds "
                         "(with a mid-stream seal) plus --delete-frac "
                         "tombstones to a segmented mutable index, then "
                         "serve the timed loop during a background merge "
                         "and differential-check against a rebuild "
                         "(DESIGN.md §2.14; implies batched mode)")
    ap.add_argument("--delete-frac", type=float, default=None, metavar="F",
                    help="paper-index: fraction of --mutate adds to "
                         "tombstone (default 0.1; requires --mutate)")
    ap.add_argument("--wal", default=None, metavar="DIR",
                    help="paper-index: durable mutable index — journal "
                         "every add/delete/seal to a write-ahead log in "
                         "DIR, checkpoint atomic snapshots, and end the "
                         "run with a crash-recovery differential "
                         "(implies --mutate; DESIGN.md §2.15)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="paper-index: deterministic fault injection — "
                         "comma-separated kind@point[:arg] rules, e.g. "
                         "'crash@wal.append.add:40' or "
                         "'transient@launch:0.05' (kinds: crash, torn, "
                         "transient, error, delay; see "
                         "repro.launch.faults; DESIGN.md §2.15)")
    ap.add_argument("--timeout-ms", type=float, default=None, metavar="MS",
                    help="paper-index: per-request deadline for --qps live "
                         "serving — requests still queued past the "
                         "deadline resolve as timed out, never hang")
    ap.add_argument("--qps", type=float, default=0.0, metavar="Q",
                    help="paper-index: open-loop live serving at offered "
                         "load Q through the continuous-batching server "
                         "(0 = offline batch mode; composes with "
                         "--mutate/--wal/--chaos/--timeout-ms)")
    ap.add_argument("--seed", type=int, default=0,
                    help="paper-index: seed for --chaos fault schedules "
                         "and --qps arrival gaps")
    ap.add_argument("--cache", action="store_true",
                    help="paper-index: serve with a DecodeCache and report "
                         "its hit rate")
    ap.add_argument("--shared-vocab", action="store_true",
                    help="paper-index: Zipf-shared query term ids "
                         "(realistic cache hit rates)")
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.arch == "paper-index":
        return serve_index(args)
    spec = get_config(args.arch)
    if spec.family == "lm":
        return serve_lm(args, spec)
    if spec.family == "recsys":
        return serve_recsys(args, spec)
    raise SystemExit(f"no serving mode for family {spec.family}")


if __name__ == "__main__":
    main()
