"""The traffic generator and percentiles, checked on the CPU.

    python -m pytest perfbench/tests/test_traffic.py -q
"""
from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import check, traffic  # noqa: E402

OPEN = {"loop": "open", "rate_per_s": 2.0, "gap_seed": 1}


def test_open_schedule_same_times_every_seed():
    a = traffic.open_schedule(OPEN, 1, 51.0, [5, 9])
    b = traffic.open_schedule(OPEN, 2**33 + 1, 51.0, [5, 9])
    assert len(a) == len(b) == 102
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert a[0].due_s == 0.0 and a[-1].due_s < 51.0
    # each pool origin takes half of the times, in one fixed order;
    # --seed decides only each request's own seed
    assert sorted(r.origin for r in a) == [5] * 51 + [9] * 51
    assert [r.origin for r in a] == [r.origin for r in b]
    assert [r.origin for r in a] != sorted(r.origin for r in a)
    assert [r.seed for r in a] != [r.seed for r in b]
    assert a == traffic.open_schedule(OPEN, 1, 51.0, [5, 9])


def test_sample_holds_the_largest_dispatch():
    reqs = [traffic.Request(5, s, 0.0) for s in range(100, 130)]
    batches = [[100], [101, 102, 103], [104, 105, 106, 107, 108], [109]]
    picked = check.sample(reqs, batches, 8, 3)
    seeds = [r.seed for r in picked]
    assert len(seeds) == len(set(seeds)) == 8
    assert set(seeds) >= {104, 105, 106, 107, 108}
    assert seeds == [r.seed for r in check.sample(reqs, batches, 8, 3)]
    assert seeds != [r.seed for r in check.sample(reqs, batches, 8, 4)]
    # fewer answers than the sample: all of them
    assert check.sample(reqs[:5], batches, 8, 3) == reqs[:5]


def test_closed_stream_is_seeded():
    tr = {"loop": "closed", "clients": 3}
    s1 = traffic.ClosedStream(tr, 7, [5, 9])
    s2 = traffic.ClosedStream(tr, 7, [5, 9])
    s3 = traffic.ClosedStream(tr, 8, [5, 9])
    got = [s1.next(c) for c in (0, 1, 2, 0)]
    assert got == [s2.next(c) for c in (0, 1, 2, 0)]
    # each client keeps its own origin; --seed changes only the seeds
    other = [s3.next(c) for c in (0, 1, 2, 0)]
    assert [o for o, _ in got] == [o for o, _ in other] == [5, 9, 5, 5]
    assert [s for _, s in got] != [s for _, s in other]


def test_percentile():
    assert traffic.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert traffic.percentile([0.0, 10.0], 90) == pytest.approx(9.0)
    assert traffic.percentile(list(range(11)), 90) == pytest.approx(
        np.percentile(range(11), 90))
    # a failed request is infinitely slow and still counts
    assert traffic.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert traffic.percentile([1.0, math.inf], 90) == math.inf
