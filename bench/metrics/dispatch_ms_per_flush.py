"""dispatch_ms_per_flush: host time in the program's ``dispatch`` spans
(the enqueue of each chunk's device program) per flush."""

from harness import progtrace


def read(run):
    spans = progtrace.spans_of(run)
    if spans is None or not run.n_flushes:
        return None
    from repro import trace
    return trace.totals_ns(spans).get("dispatch", 0) * 1e-6 / run.n_flushes
