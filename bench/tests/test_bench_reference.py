"""The plain reference: SvS answers, the least-bytes count, the comparison
that decides ``correct``, and the control that must fail it."""

from __future__ import annotations

import numpy as np
import pytest

from harness import reference


def test_svs_candidates_and_least_bytes_hand_checked():
    # shortest first: a (4), then b (5), then c (8)
    a = np.array([2, 5, 9, 14], np.int32)
    b = np.array([1, 2, 5, 9, 20], np.int32)
    c = np.array([0, 2, 3, 4, 6, 9, 11, 30], np.int32)
    answer, before = reference.svs([c, a, b])
    # a ∩ b = {2, 5, 9}: 4 candidates probe b, then 3 probe c
    assert before == [4, 3]
    assert answer.tolist() == [2, 9]
    got, nbytes = reference.answer_and_bytes([c, a, b])
    assert got.tolist() == [2, 9]
    # 4 B x (|a| = 4 + candidates 4 + 3 + |answer| = 2) = 52 B
    assert nbytes == 4 * (4 + 4 + 3 + 2)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_svs_matches_intersect1d(seed):
    rng = np.random.default_rng(seed)
    lists = [np.unique(rng.integers(0, 5000, n)).astype(np.int32)
             for n in (300, 2000, 4000)]
    want = np.intersect1d(np.intersect1d(lists[0], lists[1]), lists[2])
    got, _ = reference.svs(lists)
    assert np.array_equal(got, want)


class _Res:
    def __init__(self, docs, count=None):
        self.docs = np.asarray(docs, np.int64)
        self.count = len(docs) if count is None else count


def _ref():
    postings = [np.arange(0, 100, 2, dtype=np.int32),
                np.arange(0, 100, 3, dtype=np.int32),
                np.arange(0, 100, 5, dtype=np.int32)]
    ref = reference.Reference(postings, threads=2)
    ref.compute([[0, 1], [0, 1, 2]])
    return ref


def test_mismatches_exact_count_and_truncated_docs():
    ref = _ref()
    full = ref.answer([1, 0])                    # term order does not matter
    assert full.tolist() == list(range(0, 100, 6))
    ok = [([0, 1], _Res(full)),
          ([0, 1], _Res(full[:3], count=len(full)))]
    assert reference.mismatches(ok[:1], ref, max_results=100) == []
    assert reference.mismatches(ok[1:], ref, max_results=3) == []
    bad = [([0, 1], _Res(full[:-1])),                       # a doc lost
           ([0, 1], _Res(full, count=len(full) + 1)),       # count off
           ([0, 1], _Res(full[:3], count=len(full)))]       # cut too short
    assert reference.mismatches(bad, ref, max_results=100) == [0, 1, 2]


def test_control_fails_the_comparison():
    """The control, the reference with its longest fold skipped, answers a
    strict superset here on every query."""
    ref = _ref()
    ctrl = reference.Reference(ref.postings, threads=2, drop_longest=True)
    queries = [[0, 1], [0, 1, 2]]
    ctrl.compute(queries)
    served = [(q, _Res(ctrl.answer(q))) for q in queries]
    assert reference.mismatches(served, ref, max_results=1 << 16) == [0, 1]
