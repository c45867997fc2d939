"""resolve_ms_per_query: host time in the program's ``resolve`` spans
(term resolution of one (query, part), decode included where the
configuration decodes per query) per answered request."""

from harness import progtrace


def read(run):
    spans = progtrace.spans_of(run)
    if spans is None or not run.served():
        return None
    from repro import trace
    return trace.totals_ns(spans).get("resolve", 0) * 1e-6 / run.served()
