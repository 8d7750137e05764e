"""Percentiles with misses, and latencies timed from the due time."""
import math
import types

import pytest

from bench.metrics._common import p95_with_misses
from bench.stats import percentile, spread


def test_percentile_nearest_rank():
    assert percentile(list(range(1, 101)), 95.0) == 95
    assert percentile([3.0], 95.0) == 3.0
    assert percentile([], 95.0) is None


def test_misses_rank_above_every_answer():
    vals = list(range(1, 96))           # 95 answered
    assert percentile(vals, 95.0, misses=5) == 95
    assert percentile(vals, 95.0, misses=6) is None     # lands on a miss


def window(w0=10.0, w1=20.0, t_end=30.0):
    w = types.SimpleNamespace(w0=w0, w1=w1, t_end=t_end)
    w.in_window = lambda t: w0 <= t < w1
    return w


def test_latency_counts_from_due_and_window_by_due():
    w = window()
    # due inside the window, answered 0.5 s later; a late loop does not
    # shorten it, and a request due before the window is not counted
    pairs = [(12.0, 12.5)] * 18 + [(15.0, 17.0)] * 2 + [(5.0, 25.0)]
    assert p95_with_misses(w, pairs) == pytest.approx(2000.0)


def test_a_miss_reads_as_its_wait_to_the_end():
    w = window()
    pairs = [(12.0, 12.1)] * 10 + [(11.0, math.nan)]
    # 11 samples: the p95 rank (11) is the miss, waiting 30 - 11 s
    assert p95_with_misses(w, pairs) == pytest.approx(19000.0)


def test_spread_is_iqr_over_median():
    assert spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert spread([90.0, 100.0, 100.0, 110.0]) == pytest.approx(0.15)
