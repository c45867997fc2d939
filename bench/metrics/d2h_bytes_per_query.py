"""d2h_bytes_per_query: bytes the program copies back from the device at
collection (the ``vals`` and ``counts`` of every program, its
``d2h_bytes`` counter) per answered request."""


def read(run):
    if "d2h_bytes" not in run.counters or not run.served():
        return None
    return run.counters["d2h_bytes"] / run.served()
