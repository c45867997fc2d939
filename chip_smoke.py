"""On-chip smoke: serve ClueWeb09-B-sized conjunctive queries on a TPU.

Drives the normal serving entry point (``repro.launch.server.main``: the
continuous-batching server over a device-resident index, fused programs,
AOT warmup) on the paper's deployment — ClueWeb09 Category B's
50,000,000 documents with its Table 2 (TREC Million Query) list-length
marginals and a Zipf-shared query vocabulary — once as a drain backlog
and once under Poisson arrivals, for the jax and the pallas backends, all
four phases over the same corpus and query log.  Every phase must serve
with no error, fault, timeout, degradation, low-occupancy fallback or
steady-state compile, and every answer must equal a plain numpy
reference (``np.intersect1d`` folded over the posting lists) byte for
byte.  The q/s and latency it prints are a smoke, not a benchmark.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 4-shard fan-out vs one chip

``--four-chips`` runs only the sharded path: the 4-shard server over the
first four devices and the one-chip server on the same corpus, with
byte-identical results and every shard's resident buffers on its own
device.  The script exits non-zero, printing no result, unless JAX finds
a TPU and the Pallas kernels run compiled.  Its last line is one JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from functools import reduce

CLUEWEB09B_DOCS = 50_000_000       # index/corpus.py TABLE2_DOCS
QUERIES = 64                       # one query log, checked whole per phase
# Max batch per backend.  A Poisson-phase warm-up runs every query once
# per batch-size bucket up to the max batch, and the jax backend's
# gather-bound searchsorted is slow at this size, so jax flushes one
# query at a time.
BATCH = {"jax": 1, "pallas": 32}


def fail(msg: str):
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


def numpy_reference(corpus, qids):
    """Conjunctive answers from the raw posting lists, shortest first."""
    import numpy as np
    out = {}
    for qi in qids:
        lists = sorted((corpus.postings[t] for t in corpus.queries[qi]),
                       key=len)
        out[qi] = reduce(
            lambda a, b: np.intersect1d(a, b, assume_unique=True),
            lists).astype(np.int64)
    return out


def check_against(results, ref, max_results: int, label: str):
    import numpy as np
    for qi, want in ref.items():
        got = results[qi]
        if got is None:
            fail(f"{label}: query {qi} was not served")
        if got.count != want.shape[0] or not np.array_equal(
                got.docs, want[:max_results]):
            fail(f"{label}: query {qi} differs from the numpy reference "
                 f"({got.count} vs {want.shape[0]} docs)")


def largest_signature(server):
    """The largest fused svs ceiling the warmed plan holds, by operand
    ints — the shapes a compile test should cover."""
    best = None
    for (kind, geom), dims in server.plan.dims.items():
        if kind != "svs":
            continue
        d = dict(zip(("M", "N", "W", "J", "Jb"), dims[:5]))
        if geom is not None:
            d.update(zip(("k_pad", "t_pad", "c_pad", "e_pad", "Jp"),
                         dims[5:]))
            d.update(block_rows=geom[0], mode=geom[1])
        size = (d["M"] + d["J"] * d["N"] + d["Jb"] * d["W"]
                + d.get("Jp", 0) * (d.get("t_pad", 0) * 128
                                    + d.get("c_pad", 0)
                                    * d.get("block_rows", 0) * 128))
        if best is None or size > best[0]:
            best = (size, d)
    bp = max((sig[2] for sig in server.stats.get("signatures", ())
              if sig[0] == "svs"), default=None)
    return None if best is None else {**best[1], "max_Bp": bp}


def serve_phase(server_lib, argv: list[str], label: str, device):
    """One ``server.main`` run plus the zero-failure checks every phase
    without an injector must pass."""
    t0 = time.perf_counter()
    results, server, corpus = server_lib.main(argv)
    wall = time.perf_counter() - t0
    s = server.metrics.summary()
    st = server.stats
    wu = server.warm_report
    drain = "--qps" not in argv
    bad = {"n_errors": s["n_errors"], "n_faults": s["n_faults"],
           "n_timeout": s["n_timeout"],
           "degradations": server.ladder.n_degradations,
           "pallas_lowocc_fallbacks": st.get("pallas_lowocc_fallbacks", 0),
           "steady_compiles": st.get("n_compiles", 0)}
    if drain:
        bad["n_shed"] = s["n_shed"]
    bad = {k: v for k, v in bad.items() if v}
    if bad:
        fail(f"{label}: {bad}")
    if wu is None or not wu["converged"]:
        fail(f"{label}: warmup did not reach its signature fixed point")
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    say(f"{label}: warmup {wu['n_compiles']} compiles over "
        f"{wu['n_signatures']} signatures in {wu['time_s']:.2f} s; "
        f"0 steady-state compiles; {s['n_done']} done / {s['n_shed']} shed "
        f"in {s['n_flushes']} flushes; {wall:.1f} s wall incl. build")
    say(f"{label}: smoke, not a benchmark: {s['qps']:.1f} q/s, latency "
        f"p50 {s['p50_ms']:.2f} ms / p99 {s['p99_ms']:.2f} ms")
    say(f"{label}: folds {st.get('folds', 0)}: "
        f"{st.get('kernel_folds', 0)} in a Pallas kernel, "
        f"{st.get('kernel_vmem_fallbacks', 0)} VMEM fallbacks to jnp; "
        f"peak_bytes_in_use {peak}")
    return results, server, corpus, s


def corpus_line(corpus, n_docs: int):
    postings = sum(int(p.shape[0]) for p in corpus.postings)
    cut = ("" if n_docs == CLUEWEB09B_DOCS
           else f" (cut from ClueWeb09-B's {CLUEWEB09B_DOCS:,})")
    say(f"corpus: n_docs {n_docs:,}{cut}, {postings:,} postings over "
        f"{len(corpus.postings)} terms (the vocabulary holds only the "
        f"terms the query log uses), {len(corpus.queries)} queries")


def reference_for(corpus, n_docs: int):
    corpus_line(corpus, n_docs)
    t0 = time.perf_counter()
    ref = numpy_reference(corpus, range(len(corpus.queries)))
    say(f"numpy reference for {len(ref)} queries in "
        f"{time.perf_counter() - t0:.1f} s")
    return ref


def serve_args(n_docs: int, backend: str) -> list[str]:
    return ["--n-docs", str(n_docs), "--queries", str(QUERIES),
            "--shared-vocab", "--warmup", "--batch", str(BATCH[backend]),
            "--seed", "0", "--backend", backend]


def one_chip(server_lib, device, n_docs: int = CLUEWEB09B_DOCS):
    ref = largest = None
    for backend in BATCH:
        argv = serve_args(n_docs, backend) + ["--resident"]
        results, server, corpus, s = serve_phase(
            server_lib, argv, f"{backend}/drain", device)
        if ref is None:
            ref = reference_for(corpus, n_docs)
        check_against(results, ref, server.max_results, f"{backend}/drain")
        say(f"{backend}/drain: {len(ref)} queries byte-identical to the "
            f"numpy reference")
        if backend == "pallas":
            largest = largest_signature(server)
            if not server.stats.get("kernel_folds"):
                fail("pallas/drain: no fold ran in a Pallas kernel")
        qps = max(round(0.5 * s["qps"], 2), 0.1)
        del results, server, corpus
        gc.collect()
        label = f"{backend}/poisson@{qps:g}"
        results, server, _, _ = serve_phase(
            server_lib, argv + ["--qps", str(qps), "--pattern", "poisson"],
            label, device)
        check_against(results, ref, server.max_results, label)
        say(f"{label}: {len(ref)} queries byte-identical to the numpy "
            f"reference")
        del results, server
        gc.collect()
    say(f"largest fused signature: {json.dumps(largest)}")


def four_chips(server_lib, devices, n_docs: int = CLUEWEB09B_DOCS):
    import numpy as np
    if len(devices) < 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devices)}")
    base = serve_args(n_docs, "pallas")
    one, server1, corpus, _ = serve_phase(
        server_lib, base + ["--resident"], "1 chip/drain", devices[0])
    ref = reference_for(corpus, n_docs)
    check_against(one, ref, server1.max_results, "1 chip/drain")
    del server1, corpus
    gc.collect()
    four, server4, _, _ = serve_phase(server_lib, base + ["--shards", "4"],
                                      "4 shards/drain", devices[0])
    sharded = server4.sharded
    placement = [str(d) for d in sharded.placement]
    if len(set(placement)) != 4 or set(sharded.placement) != set(devices[:4]):
        fail(f"shards are not one per device: {placement}")
    for sid, pool in enumerate(sharded.pools):
        bufs = ([e["dev"] for e in pool._store.values()]
                + [a._buf for a in pool._arenas.values()
                   if a._buf is not None])
        if not bufs:
            fail(f"shard {sid} holds no resident buffer")
        where = {d for b in bufs for d in b.devices()}
        if where != {pool.device}:
            fail(f"shard {sid} buffers on {where}, expected {pool.device}")
        peak = (pool.device.memory_stats() or {}).get("peak_bytes_in_use")
        say(f"shard {sid} -> {pool.device}: {len(bufs)} resident buffers, "
            f"{pool.device_ints():,} ints, all on its device; "
            f"peak_bytes_in_use {peak}")
    for qi, (a, b) in enumerate(zip(one, four)):
        if a is None or b is None or a.count != b.count or \
                not np.array_equal(a.docs, b.docs):
            fail(f"query {qi}: 4-shard result differs from one chip")
    check_against(four, ref, server4.max_results, "4 shards/drain")
    say(f"4 shards: {len(four)} results byte-identical to the one-chip "
        f"server, {len(ref)} to the numpy reference")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard fan-out against one chip")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        fail("REPRO_PALLAS_INTERPRET is set: the smoke runs the kernels "
             "compiled only")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.launch import compile_cache, server as server_lib
    except ImportError as e:
        fail(f"the repro package is not next to chip_smoke.py ({e})")
    cache_dir = compile_cache.enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r} "
             f"({dev.device_kind})")
    from repro.kernels import ops as kernel_ops
    if kernel_ops.kernel_mode() != "compiled":
        fail(f"Pallas kernels would run in {kernel_ops.kernel_mode()} mode")
    say(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
        f"kernel_mode {kernel_ops.kernel_mode()}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(server_lib, devices)
    else:
        one_chip(server_lib, dev)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
