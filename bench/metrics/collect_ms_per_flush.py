"""collect_ms_per_flush: host time inside ``batch.collect_batch`` (the
wait for the device, the copy back, the per-row extraction) per flush,
from the harness's spans around it."""


def read(run):
    if not run.spans or not run.spans["collect"]:
        return None
    spans = run.spans["collect"]
    return sum(b - a for a, b in spans) * 1e3 / len(spans)
