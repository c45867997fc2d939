"""The deployment's data: posting lists and a query log shaped by the
paper's Table 2 (arXiv:1401.6399, ClueWeb09 / TREC Million Query).

The benchmark makes its own data, so the plain reference never reads
anything the program made.  The log's *shape* (how many terms each query
has, the target length of every term's list, which terms each query names)
is drawn from the configuration's ``log_seed``, and the doc-id gaps of
every list from its ``data_seed``.  ``--seed`` draws the doc ids and the
order in which the log is served: within each block of ``BLOCK`` gaps, as
the index's codec blocks a part of a list, the seed orders the block's
gaps (one order of a block's positions per list and part).  So every seed serves other doc ids and other answers over the same
sizes: each block keeps its gaps as a multiset and its last doc, the codec
picks the same widths and exceptions, and every seed's index has the same
encoded shapes, whose programs are in the compile cache after a
checkout's first run.

The shape follows ``repro.index.corpus.synthesize(shared_vocab=True)``:
query arity from Table 2's query shares; per position a target length
from Table 2's mean hits, scaled to ``n_docs`` and jittered log-normally;
terms shared through a small vocabulary per power-of-two length bucket,
reused with Zipf weights over creation rank.  One departure: every list
has exactly its target length inside ``[0, n_docs)`` (the program's
generator draws over the next power of two and drops what falls past
``n_docs``, leaving about three quarters of the target).
"""

from __future__ import annotations

import dataclasses

import numpy as np

# gaps per codec block: FastPFOR-d1 packs 32 rows of 128 lanes
BLOCK = 4096


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@dataclasses.dataclass
class LogShape:
    term_sizes: list[int]          # term id -> list length
    queries: list[list[int]]       # query -> term ids, Table 2 position order


@dataclasses.dataclass
class Corpus:
    n_docs: int
    postings: list[np.ndarray]     # term id -> sorted unique int32 doc ids
    log: list[list[int]]           # the served order of the query log

    @property
    def n_postings(self) -> int:
        return sum(int(p.shape[0]) for p in self.postings)


def log_shape(cfg: dict) -> LogShape:
    """Term sizes and query term sets, from the configuration alone."""
    rng = np.random.default_rng(cfg["log_seed"])
    table = {int(k): v for k, v in cfg["table2"].items()}
    n_docs = cfg["n_docs"]
    scale = n_docs / cfg["table2_docs"]
    sigma = cfg["length_jitter_sigma"]
    per_bucket = cfg["vocab_terms_per_bucket"]
    zipf_s = cfg["vocab_zipf_s"]
    arities = list(table)
    probs = np.array([table[k][0] for k in arities], dtype=np.float64)
    probs /= probs.sum()
    sizes: list[int] = []
    vocab: dict[int, list[int]] = {}
    queries: list[list[int]] = []
    for _ in range(cfg["log_queries"]):
        k = int(rng.choice(arities, p=probs))
        tids: list[int] = []
        for mean_k in table[k][1]:
            target = int(mean_k * 1000 * scale
                         * float(np.exp(rng.normal(0, sigma))))
            target = min(max(target, 4), n_docs - 1)
            bucket = vocab.setdefault(int(np.log2(target)), [])
            free = [t for t in bucket if t not in tids]
            if len(bucket) < per_bucket or not free:
                tid = len(sizes)
                sizes.append(target)
                bucket.append(tid)
            else:
                w = np.array([1.0 / (i + 1) ** zipf_s
                              for i, t in enumerate(bucket) if t in free])
                tid = free[int(rng.choice(len(free), p=w / w.sum()))]
            tids.append(tid)
        queries.append(tids)
    return LogShape(sizes, queries)


def cluster_list(rng: np.random.Generator, n: int, universe: int,
                 cluster_size: int = 32) -> np.ndarray:
    """``n`` sorted distinct ints in ``[0, universe)``, ClusterData-style
    (paper §6.5, after Anh and Moffat): runs of small gaps, uniform in
    ``[1, universe / n]``, broken by large jumps that spend the rest of
    the universe."""
    small_max = max(universe // n, 2)
    gaps = rng.integers(1, small_max + 1, size=n, dtype=np.int32)
    budget = universe - 1 - int(gaps.sum(dtype=np.int64))
    if budget < 0:
        # too dense for the gap process: draw the members uniformly
        return np.sort(rng.choice(universe, size=n, replace=False)
                       ).astype(np.int32)
    n_clusters = max(n // cluster_size, 1)
    starts = rng.integers(0, n, size=n_clusters)
    w = rng.random(n_clusters)
    big = np.floor(w / w.sum() * budget).astype(np.int32)
    gaps[starts] += big            # a repeated start keeps one jump: still < U
    vals = np.cumsum(gaps, dtype=np.int32)   # the total stays under U < 2**31
    vals -= 1
    return vals


def shuffle_gaps(rng: np.random.Generator, docs: np.ndarray, n_docs: int,
                 n_parts: int, block: int = BLOCK) -> np.ndarray:
    """``docs`` with the gaps of each block reordered by ``rng``.  Blocks
    are counted as the index counts them: within each of ``n_parts`` equal
    doc ranges, from the range's start, ``block`` gaps at a time.  Per
    part, one order of a block's positions drawn from ``rng`` reorders
    every full block (a gather, cheap at 259M postings), and another the
    last, partial one.  A block keeps its multiset of gaps and its last
    doc; a part's first gap stays first when it is 0 (the range's first
    doc is a member)."""
    bounds = np.linspace(0, n_docs, n_parts + 1).astype(np.int64)
    cuts = np.searchsorted(docs, bounds)
    out = np.empty_like(docs)
    for p in range(n_parts):
        seg = docs[cuts[p]:cuts[p + 1]]
        if seg.size < 2:
            out[cuts[p]:cuts[p + 1]] = seg
            continue
        gaps = np.diff(seg, prepend=np.int32(bounds[p]))
        full = seg.size // block * block
        if full:
            gaps[:full] = np.take(gaps[:full].reshape(-1, block),
                                  rng.permutation(block), axis=1).reshape(-1)
        gaps[full:] = gaps[full:][rng.permutation(seg.size - full)]
        head = gaps[:block]
        zero = np.flatnonzero(head == 0)
        if zero.size:                      # only a part's first gap is 0
            head[zero[0]], head[0] = head[0], 0
        gaps[0] += bounds[p]
        np.cumsum(gaps, out=out[cuts[p]:cuts[p + 1]])
    return out


def synthesize(cfg: dict, seed: int, shape: LogShape | None = None
               ) -> Corpus:
    """The corpus and the served order of the log for one ``--seed``;
    each term's list draws from generators of its own."""
    shape = shape or log_shape(cfg)
    n_parts = cfg["index"]["n_parts"]
    postings = [shuffle_gaps(rng_for(seed, (1 << 32) + tid),
                             cluster_list(rng_for(cfg["data_seed"], 16 + tid),
                                          n, cfg["n_docs"],
                                          cfg["cluster_size"]),
                             cfg["n_docs"], n_parts)
                for tid, n in enumerate(shape.term_sizes)]
    order = rng_for(seed, 1).permutation(len(shape.queries))
    return Corpus(cfg["n_docs"], postings,
                  [list(shape.queries[i]) for i in order])
