"""Fault-injection seam + server-resilience coverage (DESIGN.md §2.15).

Layers:
  * the injector itself: spec-string parsing, the point registry (crash
    and torn refused at server seams, torn refused off the WAL), counted
    rules counting from arm time, seeded-probability determinism, the
    merge-hook adapter,
  * the degradation ladder state machine: threshold-gated step-downs, the
    cooldown gate, one promotion per quiet period,
  * server end-to-end: transient faults retry to success with ZERO lost
    requests and byte-identical answers; persistent errors resolve every
    admitted request (never hang the loop); collect-seam faults resolve
    as errors; after the breaker degrades and re-promotes, steady-state
    serving compiles nothing.
"""

import asyncio

import numpy as np
import pytest

from repro.index import batch as batch_lib
from repro.index import builder, corpus as corpus_lib, engine
from repro.launch import faults
from repro.launch import server as server_lib

pytestmark = [pytest.mark.server, pytest.mark.faults]


@pytest.fixture(scope="module")
def uniform():
    corpus = corpus_lib.synthesize(n_docs=1 << 14, n_queries=12, seed=33)
    idx = builder.build(corpus.postings, corpus.n_docs,
                        codec_name="fastpfor-d1", B=16, n_parts=2)
    seq = [engine.query(idx, q) for q in corpus.queries]
    return idx, corpus.queries, seq


def _assert_identical(results, seq):
    assert len(results) == len(seq)
    for got, want in zip(results, seq):
        assert got.count == want.count
        assert np.array_equal(got.docs, want.docs)


# --------------------------------------------------------------------------
# the injector
# --------------------------------------------------------------------------

def test_spec_parsing_arms_rules():
    inj = faults.FaultInjector(
        "crash@wal.append.add:3, transient@launch:0.5,delay@collect:2")
    assert inj.armed == 3
    assert inj.counts() == {}


@pytest.mark.parametrize("spec", [
    "explode@launch",                   # unknown kind
    "crash@nowhere",                    # unknown point
    "crash@launch",                     # crash at a server seam
    "torn@snapshot.write",              # torn off the WAL
    "crash-wal.append.add",             # malformed clause
])
def test_bad_specs_rejected(spec):
    with pytest.raises(ValueError):
        faults.FaultInjector(spec)


def test_counted_rule_counts_from_arm_time():
    inj = faults.FaultInjector()
    inj.fire("wal.append.add")          # pre-arm traffic must not count
    inj.arm("crash", "wal.append.add", 3)
    inj.fire("wal.append.add")
    inj.fire("wal.append.add")
    with pytest.raises(faults.InjectedCrash):
        inj.fire("wal.append.add")
    assert inj.armed == 0               # one-shot: consumed on firing
    inj.fire("wal.append.add")          # and quiet afterwards
    assert inj.counts() == {"crash@wal.append.add": 1}
    assert inj.hits["wal.append.add"] == 5


def test_transient_first_n_hits_then_clean():
    inj = faults.FaultInjector("transient@launch:2")
    for _ in range(2):
        with pytest.raises(faults.TransientFault):
            inj.fire("launch")
    inj.fire("launch")                  # exhausted
    assert inj.counts() == {"transient@launch": 2}


def test_probability_rule_is_seed_deterministic():
    def run(seed):
        inj = faults.FaultInjector("transient@launch:0.3", seed=seed)
        out = []
        for _ in range(64):
            try:
                inj.fire("launch")
                out.append(0)
            except faults.TransientFault:
                out.append(1)
        return out
    a, b = run(7), run(7)
    assert a == b and 0 < sum(a) < 64   # same schedule, actually mixed
    assert run(8) != a                  # a different seed reschedules


def test_merge_hook_adapter_chains_inner():
    inj = faults.FaultInjector()
    inj.arm("crash", "merge.build", 1)
    seen = []
    hook = inj.merge_hook(inner=seen.append)
    hook("snapshot")
    hook("decode")
    with pytest.raises(faults.InjectedCrash):
        hook("build")
    assert seen == ["snapshot", "decode", "build"]   # inner always runs


# --------------------------------------------------------------------------
# the degradation ladder
# --------------------------------------------------------------------------

def test_degradation_ladder_state_machine():
    t = [0.0]
    lad = server_lib.DegradationLadder("pallas", True, threshold=2,
                                       cooldown_s=1.0, clock=lambda: t[0])
    assert lad.levels == [("pallas", True), ("pallas", False),
                          ("jax", False)]
    lad.on_failure()
    assert lad.level == 0               # below threshold: hold the rung
    lad.on_failure()
    assert lad.level == 1 and lad.n_degradations == 1
    lad.on_failure()
    lad.on_failure()
    assert lad.level == 2
    lad.on_failure()
    lad.on_failure()
    assert lad.level == 2               # already at the bottom rung
    lad.on_success()
    assert lad.level == 2               # cooldown not yet quiet
    t[0] += 1.5
    lad.on_success()
    assert lad.level == 1 and lad.n_promotions == 1
    lad.on_success()
    assert lad.level == 1               # one promotion per cooldown
    t[0] += 1.5
    lad.on_success()
    assert lad.level == 0 and lad.current == ("pallas", True)


def test_ladder_failure_rearms_cooldown():
    t = [0.0]
    lad = server_lib.DegradationLadder("jax", True, threshold=1,
                                       cooldown_s=1.0, clock=lambda: t[0])
    lad.on_failure()
    assert lad.level == 1
    t[0] += 0.9
    lad.on_failure()                    # at the bottom, but quiet restarts
    t[0] += 0.9                         # 1.8 since degrade, 0.9 since fail
    lad.on_success()
    assert lad.level == 1               # the new cooldown is not over
    t[0] += 0.2
    lad.on_success()
    assert lad.level == 0


# --------------------------------------------------------------------------
# server end-to-end resilience
# --------------------------------------------------------------------------

def test_server_transient_faults_retry_zero_lost(uniform):
    idx, queries, seq = uniform
    inj = faults.FaultInjector("transient@launch:3", seed=0)
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=1024, injector=inj, max_retries=6,
        retry_backoff_ms=0.1)
    results = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    m = srv.metrics
    assert m.n_faults == 3 and m.n_retries == 3
    assert m.n_errors == 0 and m.n_shed == 0
    outs = srv.outcomes()
    assert outs == ["done"] * len(queries)       # zero lost requests
    _assert_identical(results, seq)              # and byte-identical


def test_server_retry_exhaustion_resolves_as_errors(uniform):
    idx, queries, _ = uniform
    inj = faults.FaultInjector("transient@launch:1000000", seed=0)
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=1024, injector=inj, max_retries=2,
        retry_backoff_ms=0.1)
    results = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    assert all(r is None for r in results)
    outs = srv.outcomes()
    assert set(outs) == {"error"} and len(outs) == len(queries)
    assert srv.metrics.n_errors == len(queries)
    assert srv.metrics.n_retries > 0


def test_server_persistent_error_never_hangs(uniform):
    """A non-retryable fault resolves the whole flush as errors — the
    batcher survives and later flushes still run."""
    idx, queries, _ = uniform
    inj = faults.FaultInjector("error@launch:1000000", seed=0)
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=1024, injector=inj)
    results = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    assert all(r is None for r in results)
    assert srv.outcomes() == ["error"] * len(queries)
    assert srv.metrics.n_flushes >= 2            # the loop kept flushing


def test_server_collect_seam_fault_resolves_as_errors(uniform):
    idx, queries, _ = uniform
    inj = faults.FaultInjector("error@collect:1", seed=0)
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=1024, injector=inj)
    results = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    outs = srv.outcomes()
    assert "pending" not in outs
    assert outs.count("error") == 4              # exactly one failed flush
    assert outs.count("done") == len(queries) - 4
    done = [(q, r, s) for q, r, s in zip(queries, results,
                                         srv.outcomes()) if s == "done"]
    for q, r, _ in done:
        assert r is not None


def test_server_degrades_and_repromotes_to_zero_compiles(uniform):
    """The breaker walks down the ladder under a fault burst, promotes
    back after the cooldown, and — the acceptance bar — steady-state
    serving after re-promotion compiles nothing."""
    idx, queries, seq = uniform
    inj = faults.FaultInjector("transient@launch:4", seed=0)
    stats: dict = {}
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=1024, injector=inj, max_retries=8,
        retry_backoff_ms=0.1, breaker_threshold=2, cooldown_ms=0.0,
        stats=stats)
    server_lib.warm_server(srv, queries)
    results = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    m = srv.metrics
    assert m.n_faults == 4 and m.n_retries == 4
    assert srv.ladder.n_degradations >= 1        # the burst walked it down
    assert srv.ladder.n_promotions >= 1
    assert srv.ladder.level == 0                 # and it walked back up
    assert m.degraded_flushes >= 1
    assert srv.outcomes() == ["done"] * len(queries)
    _assert_identical(results, seq)              # degraded answers identical
    # steady state after re-promotion: the same stream compiles nothing
    stats.pop("n_compiles", None)
    results2 = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    assert stats.get("n_compiles", 0) == 0
    assert srv.outcomes() == ["done"] * len(queries)
    _assert_identical(results2, seq)


def test_server_timeout_outcomes_counted(uniform):
    """Per-request deadlines: an expired request resolves as ``timeout``
    with its ``done`` event set — never served, never hung."""
    idx, queries, _ = uniform
    srv = server_lib.ContinuousBatchingServer(
        idx, max_batch=4, max_queue=1024, timeout_ms=1e-4)
    results = asyncio.run(srv.run(queries, [0.0] * len(queries)))
    assert all(r is None for r in results)
    assert srv.outcomes() == ["timeout"] * len(queries)
    assert srv.metrics.n_timeout == len(queries)
    s = srv.metrics.summary()
    assert s["n_timeout"] == len(queries) and s["n_done"] == 0
