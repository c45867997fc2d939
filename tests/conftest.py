import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


def sorted_unique(rng, n, universe_bits=26):
    u = 1 << universe_bits
    return np.sort(rng.choice(u, size=min(n, u // 2), replace=False)).astype(
        np.int64)


# The probe's chunk size while ``probe_edges`` is in use: small enough that
# the CPU-sized seeds below cross chunk edges (M = 128 or 256 holds 4 or 8).
PROBE_EDGE_CHUNK = 32


@pytest.fixture(scope="session")
def probe_edge_corpus():
    """``make_probe_edge_corpus``, built once per test run."""
    return make_probe_edge_corpus()


def make_probe_edge_corpus():
    """Two parts of 2048 docs, B = 8.  Bitmaps (terms 0-3): term k holds
    every doc d with d % 5 != k, in both parts.  Lists (terms 4-10): in each
    part seeds of 1, C - 1, C, C + 1, 128 (= M) and 200 docs, drawn from the
    part's first 512 docs, and a term that is empty in part 0 (a seed of
    length 0 there) with 40 docs in part 1.  Every list's last doc is
    ≡ 0 (mod 5), outside bitmap 0: a chunk the probe wrongly skips keeps
    it as a phantom hit.  Queries pair each seed with 0 to 4 bitmaps.
    Terms 11-12 are seeds of 127 and 129 docs, either side of a 128-lane
    tile edge of the fold megakernels, folded against the lists above:
    their last doc is in no other list, so a tile the fold walk wrongly
    skips keeps it as a phantom hit too."""
    from repro.index import corpus as corpus_lib
    rng = np.random.default_rng(41)
    n_docs, span = 4096, 2048
    docs = np.arange(n_docs)
    postings = [docs[docs % 5 != k] for k in range(4)]

    def pick(n, end=510):
        out = []
        for lo in (0, span):
            last = lo + end - (lo + end) % 5
            body = rng.choice(np.arange(lo, lo + 500), n - 1, replace=False)
            out.append(np.sort(np.append(body, last)))
        return np.concatenate(out)

    C = PROBE_EDGE_CHUNK
    for n in (1, C - 1, C, C + 1, 128, 200):
        postings.append(pick(n))                         # terms 4-9
    postings.append(pick(40)[40:])                       # term 10
    for n in (127, 129):
        postings.append(pick(n, end=505))                # terms 11-12
    queries = [[4, 0, 1, 2, 3], [7, 0], [5, 0, 1], [6, 0, 1, 2], [8, 0, 3],
               [7, 9], [8, 9, 0], [10, 1, 0], [9, 2], [7, 8, 0, 1, 2, 3],
               [5, 7, 2], [6, 3],
               [11, 9], [12, 9], [12, 9, 1], [8, 12], [11, 12], [12, 8, 9]]
    return corpus_lib.Corpus(n_docs, [p.astype(np.int32) for p in postings],
                             queries)


@pytest.fixture
def probe_edges(probe_edge_corpus, monkeypatch):
    """``probe_edge_corpus`` with the bitmap probe's chunk cut to
    ``PROBE_EDGE_CHUNK`` for the test."""
    from repro.index import batch as batch_lib
    monkeypatch.setattr(batch_lib, "PROBE_CHUNK", PROBE_EDGE_CHUNK)
    return probe_edge_corpus
