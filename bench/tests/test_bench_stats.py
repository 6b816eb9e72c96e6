"""Due-time percentiles (``bench/stats.py``) on synthetic timestamps."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.stats import percentile, since_due  # noqa: E402


def test_nearest_rank_percentile():
    xs = list(range(1, 101))            # 1..100
    assert percentile(xs, 95) == 95
    assert percentile(xs, 50) == 50
    assert percentile(xs, 100) == 100
    assert percentile([7.0], 95) == 7.0
    assert percentile([], 95) is None
    assert percentile(reversed(xs), 1) == 1
    with pytest.raises(ValueError):
        percentile(xs, 0)


def test_latency_runs_from_due_and_counts_unfinished_as_infinite():
    recs = [{"due": 10.0, "t_first": 10.5, "t_done": 12.0, "priority": 0},
            {"due": 11.0, "t_first": 11.1, "t_done": 11.4, "priority": 2},
            {"due": 12.0, "t_first": None, "t_done": None, "priority": 0}]
    lat = since_due(recs, "due", "t_done")
    assert lat[:2] == [2.0, pytest.approx(0.4)]
    assert math.isinf(lat[2])
    urgent = since_due(recs, "due", "t_first",
                       where=lambda r: r["priority"] == 0)
    assert urgent[0] == 0.5 and math.isinf(urgent[1])
    # the tail over all requests due is the unfinished one
    assert math.isinf(percentile(lat, 95))
