"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the metrics, as one result dict.

The order keeps each number honest: set-up (data, build, staging, warm-up)
ends when the first timed request is due; the window runs with the
profiler off unless ``trace``; device memory is read when the window has
closed; then the program's state is freed and the reference runs on the
host, outside every timed span.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import sys
import tempfile
import time

import numpy as np

from harness import corpus as corpus_lib
from harness import devtrace, reference, spec, system, traffic


class Refused(Exception):
    """The run cannot measure what the cell asks for (no chip, too few
    chips, kernels not compiled, a device with no published peaks)."""


def say(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a metric reader sees.  Times are perf_counter seconds."""
    cell: str
    seconds: float
    setup_s: float
    loop: str                          # "open" | "closed"
    max_batch: int
    window: traffic.Window
    n_flushes: int
    counters: dict                     # the server's stats, window only
    compiles: int                      # XLA compiles (or cache loads)
    peak_bytes: int | None
    postings: int
    peaks: dict | None
    spans: dict | None = None          # layer -> [(t0, t1)], traced run
    trace: dict | None = None          # devtrace.reduce(...), traced run
    least_bytes: int | None = None     # reference bytes of served queries
    held_bytes: int | None = None      # device bytes in use after the window

    def served(self) -> int:
        """Requests answered, the closed loop's last one included."""
        return sum(t.ok for t in self.window.requests + self.window.after)

    @staticmethod
    def pctl(values, q: float) -> float | None:
        """The q-th percentile, linear between order statistics; None for
        no values.  An infinite value (a failed request) counts as the
        slowest, and a percentile that reaches one is infinite."""
        if not len(values):
            return None
        v = np.sort(np.asarray(values, np.float64))
        pos = (len(v) - 1) * q / 100.0
        lo, hi = int(np.floor(pos)), int(np.ceil(pos))
        if not np.isfinite(v[hi]):
            return math.inf
        return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))

    def latencies_ms(self) -> list[float]:
        """Every window request's latency from its due time; a request
        that failed counts as infinitely late."""
        return [((t.req.t_done - t.due) * 1e3 if t.ok else math.inf)
                for t in self.window.requests]


def check_device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if not require_tpu:
        return devs, None
    if dev.platform != "tpu":
        raise Refused(f"no TPU: JAX found platform {dev.platform!r} "
                      f"({dev.device_kind})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        raise Refused("REPRO_PALLAS_INTERPRET is set: kernels must run "
                      "compiled")
    from repro.kernels import ops
    if ops.kernel_mode() != "compiled":
        raise Refused(f"Pallas kernels would run in {ops.kernel_mode()} "
                      "mode")
    try:
        return devs, spec.peaks(dev.device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program
    is cached, however quickly it compiled."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts XLA executables built or loaded from the persistent cache
    while ``on`` (jax's backend-compile event)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


@dataclasses.dataclass
class Setup:
    """A built, staged and warmed system, ready for windows."""
    cell: spec.Cell
    seed: int
    devs: list
    peaks: dict | None
    corpus: corpus_lib.Corpus
    server: object
    index: object
    warm: dict
    counter: _CompileCounter


def setup(cell: spec.Cell, seed: int, require_tpu: bool = True) -> Setup:
    devs, peaks = check_device(cell.chips, require_tpu)
    cfg = cell.config
    counter = _CompileCounter()
    t = time.perf_counter()
    corp = corpus_lib.synthesize(cfg, seed)
    say(f"{cell.config_name}: {corp.n_docs:,} docs, {corp.n_postings:,} "
        f"postings over {len(corp.postings)} terms, log of "
        f"{len(corp.log)} queries, synthesized in "
        f"{time.perf_counter() - t:.2f} s")
    server, index = system.build(cfg, corp, say=say)
    warm = system.warm(server, index, corp.log, cfg["index"]["n_parts"],
                       say=say)
    # what set-up built lives as long as the server: keep it out of the
    # collector's passes in the window
    gc.collect()
    gc.freeze()
    return Setup(cell, seed, devs, peaks, corp, server, index, warm,
                 counter)


def window(su: Setup, mix: dict, seconds: float, trace_dir: str | None
           ) -> tuple[traffic.Window, system.Spans | None, float]:
    """Offer ``mix`` for ``seconds``; with ``trace_dir`` under the
    profiler and the harness's spans.  Returns the window, the spans and
    the traced length in seconds."""
    import jax
    server = su.server
    kind = mix["kind"]
    spans = None
    if trace_dir is not None:
        spans = system.Spans()
        spans.attach(server)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if kind in traffic.OPEN_KINDS:
        offsets = traffic.due_offsets(mix, seconds)
    su.counter.n = 0
    su.counter.on = True
    t0 = time.perf_counter()
    if kind in traffic.OPEN_KINDS:
        win = traffic.open_loop(server, su.corpus.log, offsets, seconds)
    elif kind == "closed":
        win = traffic.closed_loop(server, su.corpus.log,
                                  int(mix["clients"]), seconds)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    traced = time.perf_counter() - t0
    su.counter.on = False
    if trace_dir is not None:
        jax.profiler.stop_trace()
        spans.detach()
    return win, spans, traced


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> dict:
    """One run; returns the result line as a dict."""
    su = setup(cell, seed, require_tpu)
    server = su.server
    dev = su.devs[0]
    tmp = (tempfile.TemporaryDirectory(prefix="bench-trace-") if trace
           else None)
    setup_s = time.perf_counter() - t_start
    win, spans, traced = window(su, cell.traffic, seconds,
                                tmp.name if tmp else None)
    gc.collect()
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    run = Run(cell=cell.name, seconds=seconds, setup_s=setup_s,
              loop="closed" if cell.traffic["kind"] == "closed" else "open",
              max_batch=server.max_batch, window=win,
              n_flushes=server.metrics.n_flushes,
              counters=dict(server.stats), compiles=su.counter.n,
              peak_bytes=peak, held_bytes=mem.get("bytes_in_use"),
              postings=su.corpus.n_postings,
              peaks=su.peaks, spans=spans.spans if spans else None)
    served = [(t.terms, t.req.result) for t in win.requests + win.after
              if t.ok]
    max_results = server.max_results
    pool = server.pool.stats() if server.pool is not None else {}
    say(f"window: {len(win.requests)} requests ({run.loop} loop), "
        f"{run.n_flushes} flushes, {run.compiles} compiles, counters "
        f"{ {k: v for k, v in run.counters.items() if k != 'signatures'} }"
        f", peak_bytes_in_use {peak}, bytes_in_use {run.held_bytes}, "
        f"pool arena rows "
        f"{su.warm.get('pool', {}).get('arena_rows')} -> "
        f"{pool.get('arena_rows')}, evicted {pool.get('evicted_lists')}")
    if run.loop == "open":
        # the tail is read in every run but bounded by no metric: stalls
        # of 1-3 s in some runs spread it too widely (PERF.md)
        say(f"latency from due: p95 {run.pctl(run.latencies_ms(), 95)} ms,"
            f" p99 {run.pctl(run.latencies_ms(), 99)} ms (unbounded)")
    postings = su.corpus.postings
    su.server = su.index = server = None
    gc.collect()

    if tmp is not None:
        t = time.perf_counter()
        run.trace = devtrace.reduce(devtrace.load(tmp.name), traced)
        tmp.cleanup()
        say(f"trace reduced in {time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    ref = reference.Reference(postings)
    ref.compute(q for q, _ in served)
    bad = reference.mismatches(served, ref, max_results)
    run.least_bytes = sum(ref.least_bytes(q) for q, _ in served)
    say(f"reference: {len(ref.answers)} distinct queries in "
        f"{time.perf_counter() - t:.2f} s")
    attempted = len(win.requests) + len(win.after)
    unanswered = attempted - len(served)
    checks = {"mismatched_answers": {"value": len(bad), "limit": 0},
              "unanswered_requests": {"value": unanswered, "limit": 0}}

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.reader(m["name"])(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(su.devs), "memory_peak_bytes": peak}
    result = {"correct": not bad and not unanswered,
              "attempted": attempted, "failed": unanswered,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
