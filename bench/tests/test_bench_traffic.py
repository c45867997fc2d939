"""Traffic and data are deterministic from the seed, and requests are
timed from their due times."""

from __future__ import annotations

import asyncio
import json
import time
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from harness import corpus, runner, traffic

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_due_offsets_fixed_by_the_mix(kind):
    mix = {"kind": kind, "rate_qps": 40.0, "burst": 8, "arrival_seed": 7}
    a = traffic.due_offsets(mix, 5.0)
    b = traffic.due_offsets(mix, 5.0)
    c = traffic.due_offsets({**mix, "arrival_seed": 8}, 5.0)
    assert np.array_equal(a, b)
    assert len(a) == len(c) == 200
    assert np.all(np.diff(a) >= 0) and a[0] == 0 and a[-1] < 5.0
    assert not np.array_equal(a, c)
    if kind == "bursty":
        assert max(Counter(a).values()) == 8
    if kind == "poisson":
        # the same gaps for every arrival seed, in another order
        gaps = lambda x: np.sort(np.diff(np.append(x, 5.0)))
        assert np.allclose(gaps(a), gaps(c))
        assert np.mean(np.diff(a)) == pytest.approx(1 / 40.0, rel=0.01)


def _tiny_cfg(**kw):
    cfg = json.loads((CONFIGS / "clueweb09b-decoded.json").read_text())
    cfg.update(n_docs=1 << 16, log_queries=64)
    cfg.update(kw)
    return cfg


def test_corpus_fixed_and_log_order_from_the_seed():
    cfg = _tiny_cfg()
    a = corpus.synthesize(cfg, 5)
    b = corpus.synthesize(cfg, 5)
    c = corpus.synthesize(cfg, 2**31 + 6)
    assert all(np.array_equal(x, y) for x, y in zip(a.postings, b.postings))
    assert a.log == b.log
    # every seed serves the same sizes and queries: other doc ids, and
    # the log in another order
    assert [len(p) for p in a.postings] == [len(p) for p in c.postings]
    assert any(not np.array_equal(x, y)
               for x, y in zip(a.postings, c.postings))
    assert sorted(map(tuple, a.log)) == sorted(map(tuple, c.log))
    assert a.log != c.log
    d = corpus.synthesize({**cfg, "data_seed": 1}, 5)
    assert [len(p) for p in a.postings] == [len(p) for p in d.postings]
    assert any(not np.array_equal(x, y)
               for x, y in zip(a.postings, d.postings))
    for p in a.postings + c.postings:
        assert p.dtype == np.int32 and p[0] >= 0 and p[-1] < cfg["n_docs"]
        assert np.all(np.diff(p) > 0)


def test_shuffle_gaps_keeps_each_blocks_gaps_and_last_doc():
    # two parts of 100 docs; blocks of 4 gaps, counted from each part's
    # start; part 0 holds its first doc (a first gap of 0)
    docs = np.array([0, 3, 4, 9, 20, 22, 30, 31, 50,
                     100, 110, 111, 115, 140, 160], dtype=np.int32)
    rng = np.random.default_rng(3)
    seen = set()
    for _ in range(20):
        out = corpus.shuffle_gaps(rng, docs, 200, 2, block=4)
        assert out.dtype == np.int32 and np.all(np.diff(out) > 0)
        seen.add(out.tobytes())
        for part, lo in ((out[:9], 0), (out[9:], 100)):
            src = docs[:9] if lo == 0 else docs[9:]
            g, h = np.diff(part - lo, prepend=0), np.diff(src - lo, prepend=0)
            for i in range(0, len(g), 4):
                assert sorted(g[i:i + 4]) == sorted(h[i:i + 4])
                assert part[min(i + 3, len(g) - 1)] == \
                    src[min(i + 3, len(g) - 1)]
        assert out[0] == 0
    assert len(seen) > 5


def test_every_seed_encodes_to_the_same_shapes():
    import jax
    from repro.index import builder
    cfg = _tiny_cfg(n_docs=1 << 20, log_queries=24)

    def shapes(seed):
        c = corpus.synthesize(cfg, seed)
        ix = builder.build(c.postings, c.n_docs, codec_name="fastpfor-d1",
                           B=cfg["index"]["bitmap_max_gap"],
                           n_parts=cfg["index"]["n_parts"])
        return [(t, tp.kind, tp.n, [np.shape(x) for x in
                                    jax.tree_util.tree_leaves(tp.payload)])
                for part in ix.parts for t, tp in sorted(part.terms.items())]

    a, b = shapes(7), shapes(2**31 + 8)
    assert any(k == "list" for _, k, _, _ in a)
    assert a == b


class _Req:
    def __init__(self, terms):
        self.terms = terms
        self.t_arrive = time.perf_counter()
        self.t_admit = self.t_done = 0.0
        self.outcome = "pending"
        self.result = None
        self.done = asyncio.Event()


class _FakeServer:
    """The public surface the harness drives: ``run(queries, gaps)``
    sleeps each gap, then submits; ``submit`` admits a caller.  Each
    request is answered ``service_s`` after it is submitted, and the
    loop blocks ``stall_s`` before the ``stall_at``-th open-loop submit."""

    def __init__(self, service_s=0.002, stall_at=None, stall_s=0.0):
        self.service_s, self.stall_at, self.stall_s = \
            service_s, stall_at, stall_s
        self.requests = []
        self.in_flight = self.peak_in_flight = 0

    def _admit(self, terms):
        req = _Req(terms)
        self.in_flight += 1
        self.peak_in_flight = max(self.peak_in_flight, self.in_flight)

        def answer():
            req.t_admit = req.t_arrive
            req.t_done = time.perf_counter()
            req.outcome = "done"
            self.in_flight -= 1
            req.done.set()
        asyncio.get_running_loop().call_later(self.service_s, answer)
        return req

    async def submit(self, terms):
        return self._admit(terms)

    async def run(self, queries, gaps):
        reqs = []
        for terms, gap in zip(queries, gaps):
            if gap > 0:
                await asyncio.sleep(gap)
            if len(reqs) == self.stall_at:
                time.sleep(self.stall_s)          # the loop stalls
            reqs.append(self._admit(terms))
        while self.in_flight:
            await asyncio.sleep(0.001)
        self.requests = reqs


def test_open_loop_times_from_due_not_submit():
    server = _FakeServer(stall_at=1, stall_s=0.2)
    offsets = np.array([0.0, 0.01, 0.02, 0.03])
    log = [[0, 1], [1, 2], [2, 3]]
    win = traffic.open_loop(server, log, offsets, 0.05)
    assert [t.terms for t in win.requests] == log + log[:1]
    assert [t.due - win.t0 for t in win.requests] == \
        pytest.approx(offsets.tolist())
    run = runner.Run(cell="x", seconds=0.05, setup_s=0.0, loop="open",
                     max_batch=1, window=win, n_flushes=4, counters={},
                     compiles=0, peak_bytes=None, postings=0, peaks=None)
    lat = run.latencies_ms()
    # the stall delays every later submit; latency from the due time
    # carries it (about 200 ms), latency from submit would not
    assert lat[0] < 100
    assert all(x > 150 for x in lat[1:])
    assert all(t.req.t_done - t.req.t_arrive < 0.1 for t in win.requests)


def test_closed_loop_callers_wait_for_answers():
    server = _FakeServer(service_s=0.005)
    log = [[i, i + 1] for i in range(10)]
    win = traffic.closed_loop(server, log, clients=3, seconds=0.2)
    assert server.peak_in_flight <= 3 + 1      # + run()'s closing request
    # run()'s closing request draws the log's first query, before callers
    served = [t.terms for t in win.after] + [t.terms for t in win.requests]
    assert served == [log[i % 10] for i in range(len(served))]
    assert len(win.after) == 1 and win.after[0].due >= win.t_end
    assert all(t.ok for t in win.requests)
    assert all(t.due < win.t_end for t in win.requests)
