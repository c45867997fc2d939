"""collect_host_ms_per_flush: host time of result collection past the
wait for the device, the program's ``copy`` (device-to-host) and
``extract`` spans, per flush."""

from harness import progtrace


def read(run):
    spans = progtrace.spans_of(run)
    if spans is None or not run.n_flushes:
        return None
    from repro import trace
    tot = trace.totals_ns(spans)
    return ((tot.get("copy", 0) + tot.get("extract", 0)) * 1e-6
            / run.n_flushes)
