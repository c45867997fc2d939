"""The plain reference: conjunctive answers by set-versus-set (SvS)
intersection in numpy, shortest list first, straight from the benchmark's
own posting lists.  It imports nothing of the program.

It also yields, per query, the least HBM traffic any decoded SvS must
move: 4 bytes for every doc id of the shortest list, for every candidate
probed against each later list, and for every doc id of the answer.
That count depends only on the data, never on padding, fusion or the
kernel that runs (``device_roofline_share`` divides it by busy time).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

INT_BYTES = 4


def svs(lists: list[np.ndarray]) -> tuple[np.ndarray, list[int]]:
    """Intersect sorted unique arrays, shortest first.  Returns the answer
    and the candidate count before each fold after the first list."""
    lists = sorted(lists, key=len)
    cand = lists[0]
    before = []
    for other in lists[1:]:
        before.append(int(cand.shape[0]))
        if not cand.shape[0]:
            continue
        pos = np.searchsorted(other, cand)
        pos[pos == other.shape[0]] = 0
        cand = cand[other[pos] == cand]
    return cand, before


def answer_and_bytes(lists: list[np.ndarray]) -> tuple[np.ndarray, int]:
    """The SvS answer and the least bytes a decoded SvS moves for it."""
    answer, before = svs(lists)
    shortest = min(int(x.shape[0]) for x in lists)
    return answer, INT_BYTES * (shortest + sum(before)
                                + int(answer.shape[0]))


class Reference:
    """Answers for term-id queries over one corpus, each distinct query
    computed once, in a few host threads (numpy's search releases the
    GIL)."""

    def __init__(self, postings: list[np.ndarray], threads: int = 8,
                 drop_longest: bool = False):
        self.postings = postings
        self.threads = threads
        # the control: a plausible shortcut that skips the longest list's
        # fold, so an answer can hold docs that list does not
        self.drop_longest = drop_longest
        self.answers: dict[tuple, np.ndarray] = {}
        self.bytes: dict[tuple, int] = {}

    def _one(self, key: tuple):
        lists = sorted((self.postings[t] for t in key), key=len)
        if self.drop_longest and len(lists) > 1:
            lists = lists[:-1]
        return (key, *answer_and_bytes(lists))

    def compute(self, queries) -> None:
        keys = {tuple(sorted(q)) for q in queries} - self.answers.keys()
        with ThreadPoolExecutor(self.threads) as ex:
            for key, answer, nbytes in ex.map(self._one, sorted(keys)):
                self.answers[key] = answer
                self.bytes[key] = nbytes

    def answer(self, terms) -> np.ndarray:
        return self.answers[tuple(sorted(terms))]

    def least_bytes(self, terms) -> int:
        return self.bytes[tuple(sorted(terms))]


def mismatches(served, ref: Reference, max_results: int) -> list[int]:
    """Indices of served (terms, result) pairs whose count or docs differ
    from the reference: the count is exact, the docs are the first
    ``max_results`` matches in order."""
    bad = []
    for i, (terms, res) in enumerate(served):
        want = ref.answer(terms)
        docs = np.asarray(res.docs)
        if (int(res.count) != want.shape[0]
                or docs.shape[0] != min(want.shape[0], max_results)
                or not np.array_equal(docs, want[:max_results])):
            bad.append(i)
    return bad
