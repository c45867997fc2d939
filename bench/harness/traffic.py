"""Traffic: one general generator per ``kind`` of mix, driven by the mix's
data file (``bench/traffic/<name>.json``), and the two ways of offering it
to the server.

Open loop (``poisson``, ``bursty``): every request has a due
time fixed before the window opens.  It is submitted when due, whether or
not earlier ones have finished, and its latency runs from the due time, so
a stall that delays submission shows as latency (no coordinated
omission).  The arrivals are fixed by the mix (its rate, its
``arrival_seed``), so every run offers the same schedule; the run's seed
draws which queries fill it and the data they read.

Closed loop (``closed``): ``clients`` callers each send their next query
when the answer to the last one arrives.

Both draw queries from the log in its served order, cycling.  Only the
server's public entry points are used: ``run`` hosts the batching loop
for the window, ``submit`` admits a closed-loop caller.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time

import numpy as np

OPEN_KINDS = ("poisson", "bursty")


@dataclasses.dataclass
class Timed:
    """One request as the harness saw it (perf_counter seconds)."""
    terms: list
    due: float
    req: object = None            # the server's Request, None if shed

    @property
    def ok(self) -> bool:
        return self.req is not None and self.req.outcome == "done"


@dataclasses.dataclass
class Window:
    """Every request of one measured window, and the window's bounds.
    ``after`` holds requests sent after the window closed (the closed
    loop's last one), checked like the rest but not timed."""
    requests: list[Timed]
    t0: float
    t_end: float
    after: list[Timed] = dataclasses.field(default_factory=list)


def exponential_gaps(n: int, seconds: float, rng: np.random.Generator
                     ) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps that fill ``seconds``: the ``n``
    quantiles of the exponential distribution (a fixed set, so every seed
    offers the same gaps), scaled to sum to ``seconds``, in an order drawn
    from ``rng``."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return rng.permutation(q * (seconds / q.sum()))


def due_offsets(mix: dict, seconds: float) -> np.ndarray:
    """Due times of an open-loop mix, as offsets from the window's start;
    ``round(rate_qps * seconds)`` of them, the first at 0, in an order
    drawn from the mix's own ``arrival_seed``: every run replays the same
    arrivals, and ``--seed`` draws which queries arrive.
    ``poisson``: exponential gaps (``exponential_gaps``).  ``bursty``: the
    same count in bursts of ``burst`` arrivals sharing one due time, the
    bursts Poisson-spaced."""
    kind = mix["kind"]
    n = max(int(round(mix["rate_qps"] * seconds)), 1)
    rng = np.random.default_rng(mix.get("arrival_seed", 0))
    if kind == "poisson":
        gaps = exponential_gaps(n, seconds, rng)
        return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    if kind == "bursty":
        burst = int(mix["burst"])
        heads = -(-n // burst)
        gaps = exponential_gaps(heads, seconds, rng)
        starts = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        return np.repeat(starts, burst)[:n]
    raise ValueError(f"unknown open-loop kind {kind!r}")


def open_loop(server, log: list, offsets: np.ndarray, seconds: float
              ) -> Window:
    """Submit each query at its due time; every request is returned
    resolved."""
    queries = itertools.cycle(log)
    timed: list[Timed] = []
    t0 = None

    def terms():
        nonlocal t0
        for off in offsets:
            q = next(queries)
            if t0 is None:
                t0 = time.perf_counter()
            timed.append(Timed(q, t0 + float(off)))
            yield q

    def gaps():
        while True:
            yield max(timed[-1].due - time.perf_counter(), 0.0)

    asyncio.run(server.run(terms(), gaps()))
    for t, req in zip(timed, server.requests):
        t.req = req
    return Window(timed, t0, t0 + seconds)


def closed_loop(server, log: list, clients: int, seconds: float
                ) -> Window:
    """``clients`` callers loop submit -> answer until
    ``seconds`` pass; every request they sent is returned resolved.
    ``run`` hosts the batching loop meanwhile: it is handed one query,
    due just after the window closes, so it keeps the loop open until
    then and drains everything admitted before it."""
    queries = itertools.cycle(log)
    timed: list[Timed] = []
    bounds = {}

    async def caller(end: float):
        loop = asyncio.get_running_loop()
        while loop.time() < end:
            t = Timed(next(queries), time.perf_counter())
            timed.append(t)
            t.req = await server.submit(t.terms)
            await t.req.done.wait()

    async def main():
        loop = asyncio.get_running_loop()
        end = loop.time() + seconds
        bounds["t0"] = time.perf_counter()

        def wait():
            yield max(end + 0.05 - loop.time(), 0.0)

        host = asyncio.ensure_future(
            server.run(iter([next(queries)]), wait()))
        await asyncio.sleep(0)             # run() opens its queue
        await asyncio.gather(*(caller(end) for _ in range(clients)))
        await host

    asyncio.run(main())
    last = server.requests[0]
    after = [Timed(last.terms, last.t_arrive, last)] if last else []
    return Window(timed, bounds["t0"], bounds["t0"] + seconds, after)
