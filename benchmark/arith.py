"""Metric arithmetic: what an end-to-end number is made of.

A rate is taken over all the work and all the time of the window. A time
per operation is the median over the window's whole operations; a window
with fewer than ``min_ops`` whole operations reports nothing rather than a
median of too few.
"""
from __future__ import annotations

import statistics
from typing import Optional, Sequence, Tuple

Op = Tuple[float, float]   # (start, end) on one monotonic clock, seconds


def median_op_seconds(ops: Sequence[Op], min_ops: int = 1
                      ) -> Optional[float]:
    """Median wall of the whole operations of a window."""
    if len(ops) < max(min_ops, 1):
        return None
    return float(statistics.median(b - a for a, b in ops))


def rate_over_window(units_per_op: float, ops: Sequence[Op]
                     ) -> Optional[float]:
    """Units completed per second: every operation of the window, over the
    time from the first one's start to the last one's end (the operation
    running when the window's time is reached is finished and counted, and
    so is its time)."""
    if not ops:
        return None
    span = ops[-1][1] - ops[0][0]
    return float(units_per_op * len(ops) / span) if span > 0 else None


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, by
    ``statistics.quantiles(values, n=4)``: the contract's spread."""
    q = statistics.quantiles(values, n=4)
    return float((q[2] - q[0]) / statistics.median(values))
