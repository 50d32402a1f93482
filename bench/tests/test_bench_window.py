"""Window arithmetic: time per partition, client-side percentiles, and the
open-loop schedule that every seed shares."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import window  # noqa: E402
from bench.drivers import open_loop  # noqa: E402


def test_per_item_is_the_whole_window_over_its_partitions():
    assert window.per_item(12.0, 4) == 3.0
    with pytest.raises(ValueError):
        window.per_item(5.0, 0)


def test_latency_runs_from_the_due_time_and_failures_are_infinite():
    lat = window.latencies([(1.0, 1.5), (2.0, None), (3.0, 3.25)])
    assert lat == [0.5, math.inf, 0.25]


@pytest.mark.parametrize("q,expect", [(50, 50), (90, 90), (100, 100),
                                      (1, 1)])
def test_nearest_rank_percentile(q, expect):
    assert window.percentile(list(range(100, 0, -1)), q) == expect


def test_failures_fill_the_tail():
    vals = [float(v) for v in range(1, 91)] + [math.inf] * 10
    assert window.percentile(vals, 90) == 90.0
    assert window.percentile(vals + [math.inf], 90) == math.inf
    assert window.percentile(vals, 50) == 50.0


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = window.arrival_times(200, 50.0, np.random.default_rng(1))
    b = window.arrival_times(200, 50.0, np.random.default_rng(2 ** 40 + 3))
    assert len(a) == len(b) == 200
    assert all(x < y for x, y in zip(a, a[1:]))
    assert 0 < a[0] and a[-1] < 50.0
    gaps = lambda t: sorted(np.diff([0.0] + t))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(b))
    assert a != b


@pytest.mark.parametrize("n,weights,expect", [
    (10, [0.7, 0.3], [7, 3]),
    (100, [1, 1 / 2, 1 / 3, 1 / 4], [48, 24, 16, 12]),
    (7, [1, 1, 1], [3, 2, 2]),
])
def test_apportion_is_exact(n, weights, expect):
    got = window.apportion(n, weights)
    assert sum(got) == n
    assert got == expect


def test_open_loop_schedule_is_the_same_mix_for_every_seed():
    traffic = {"rate_rps": 4.0, "scale_weights": [1, 0.5, 1 / 3, 0.25],
               "ks": [8, 64], "k_weights": [0.7, 0.3]}
    runs = [open_loop.schedule(traffic, [12, 13, 14, 15], 8, 25.0,
                               np.random.default_rng(seed))
            for seed in (5, 3_000_000_019)]
    for reqs in runs:
        assert len(reqs) == 100
        assert sorted(r.scale for r in reqs) == sorted(
            [12] * 48 + [13] * 24 + [14] * 16 + [15] * 12)
        assert sum(r.k == 64 for r in reqs) == 30
        assert all(0 <= r.index < 8 for r in reqs)
    assert [r.scale for r in runs[0]] != [r.scale for r in runs[1]]
