"""decoded_ints_per_query: integers decoded from compressed lists per
answered request (the program's ``decoded_ints`` counter over the
window)."""


def read(run):
    if not run.served():
        return None
    return run.counters.get("decoded_ints", 0) / run.served()
