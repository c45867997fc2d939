"""p50_ms: median latency of every request due in the window, from its
due time to its answer; a request never answered counts as the slowest."""


def read(run):
    return run.pctl(run.latencies_ms(), 50)
