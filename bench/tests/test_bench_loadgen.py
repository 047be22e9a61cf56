"""CPU tests of the load generator's schedules and statistics."""

import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loadgen as LG  # noqa: E402


@pytest.mark.parametrize("arrivals", [
    {"kind": "poisson", "rate_per_s": 4.2},
])
def test_every_seed_offers_the_same_count(arrivals):
    counts = set()
    for seed in (1, 2, 2**31 + 5):
        t = LG.due_times(arrivals, seed, 51.0)
        assert np.all(np.diff(t) >= 0) and t.min() >= 0 and t.max() < 51
        counts.add(len(t))
    assert counts == {round(4.2 * 51)}


def test_same_seed_same_schedule():
    a = {"kind": "poisson", "rate_per_s": 3.0}
    assert np.array_equal(LG.due_times(a, 9, 10), LG.due_times(a, 9, 10))


def test_latency_counts_failures_as_missing():
    win = LG.Window(100.0, 10.0)
    for rid, (due, done, ok) in enumerate([(100.0, 100.5, True),
                                           (101.0, 101.2, True),
                                           (102.0, None, None),
                                           (111.0, 111.1, True)]):
        win.requests[rid] = LG.Request(rid, 0, rid, due, due, done, ok)
    lat = LG.latency_ms(win)
    assert sorted(lat)[:2] == pytest.approx([200.0, 500.0])
    assert math.isinf(max(lat)) and len(lat) == 3
    assert LG.quantile(lat, 0.5) == pytest.approx(500.0)
    assert LG.quantile([1, 2, 3, 4], 0.95) == 4
