"""The ``fold_tile_fill`` reader: the share of a full walk of every padded
seed row that the fold megakernels walk, from the program's counters."""

import pytest

from harness import spec
from harness.runner import Run


def _run(counters):
    return Run(cell="c", seconds=1.0, setup_s=0.0, loop="closed",
               max_batch=32, window=None, n_flushes=1, counters=counters,
               compiles=0, peak_bytes=None, held_bytes=None, postings=1,
               peaks={})


@pytest.mark.parametrize("counters,value", [
    ({"fold_tiles": 229, "fold_tiles_ceiling": 1000}, 22.9),
    ({"fold_tiles": 8192, "fold_tiles_ceiling": 8192}, 100.0),
    # a program without the counters, or one that walked no fold
    ({}, None),
    ({"fold_tiles": 0, "fold_tiles_ceiling": 0}, None),
])
def test_fold_tile_fill(counters, value):
    got = spec.reader("fold_tile_fill")(_run(counters))
    assert got == (None if value is None else pytest.approx(value))
