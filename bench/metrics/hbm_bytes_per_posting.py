"""hbm_bytes_per_posting: the device's bytes in use once the window has
closed and its garbage is collected (the index, the resident pool, its
arenas and the loaded programs), over the postings in the index.  Not the
peak: the peak also holds the compiler's passing allocations, which come
and go with whether a seed's programs were already in the compile
cache."""


def read(run):
    if not run.held_bytes or not run.postings:
        return None
    return run.held_bytes / run.postings
