"""flush_fill: requests answered per flush, as a share of the max batch."""


def read(run):
    if not run.n_flushes:
        return None
    return 100.0 * run.served() / run.n_flushes / run.max_batch
