"""assemble_ms_per_flush: self time of the program's ``assemble`` spans
(operand assembly of each program chunk: arena slots and gathers,
stacking, host-to-device copies) per flush."""

from harness import progtrace


def read(run):
    spans = progtrace.spans_of(run)
    if spans is None or not run.n_flushes:
        return None
    from repro import trace
    own = trace.self_totals_ns(spans)
    return own.get("assemble", 0) * 1e-6 / run.n_flushes
