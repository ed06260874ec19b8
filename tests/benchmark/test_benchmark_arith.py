"""The metric arithmetic: median of whole trains, rows over window time,
the contract's spread."""
import statistics

import pytest

from benchmark import arith


def test_train_s_is_the_median_of_the_whole_trains():
    ops = [(0.0, 7.0), (7.1, 14.3), (14.4, 21.2), (21.3, 28.9),
           (29.0, 36.1), (36.2, 43.0), (43.1, 50.4)]
    walls = [b - a for a, b in ops]
    assert arith.median_op_seconds(ops, 6) == pytest.approx(
        statistics.median(walls))


def test_fewer_whole_trains_than_asked_report_nothing():
    assert arith.median_op_seconds([(0.0, 7.0)] * 5, 6) is None
    assert arith.median_op_seconds([], 1) is None


def test_rate_is_all_rows_over_all_the_windows_time():
    # three scores of 4M rows; gaps between them count as time
    ops = [(10.0, 11.8), (12.0, 13.8), (14.0, 16.0)]
    assert arith.rate_over_window(4e6, ops) == pytest.approx(12e6 / 6.0)
    assert arith.rate_over_window(4e6, []) is None


def test_spread_is_the_quartile_distance_over_the_median():
    vals = [7.0, 7.1, 7.2, 7.3, 7.4, 7.5]
    q = statistics.quantiles(vals, n=4)
    assert arith.spread(vals) == pytest.approx((q[2] - q[0]) / 7.25)
