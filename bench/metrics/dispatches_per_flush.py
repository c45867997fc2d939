"""dispatches_per_flush: device programs launched per flush (the
program's ``n_dispatches`` counter over the window)."""


def read(run):
    if not run.n_flushes:
        return None
    return run.counters.get("n_dispatches", 0) / run.n_flushes
