"""Operation counts of the program's kernels, from shapes: what the
algorithm needs, not what a particular schedule spends. A roofline metric
divides these by device seconds and a peak of ``peaks.json``.

Each ``*_flops`` function that a ``layer_metrics`` file names under
``opcount`` takes one span's attributes (the program's own: shapes and, for
a solver with a fixed schedule, how many contractions it ran) and returns
floating-point operations; it raises ``KeyError`` where an attribute is
missing, which a reader takes as nothing to read.
"""
from __future__ import annotations

from typing import Any, Dict


def contraction_flops(rows: int, features: int, columns: int) -> float:
    """One (rows, features) x (features, columns) product, or its
    transpose's: a multiply and an add per term."""
    return 2.0 * float(rows) * float(features) * float(columns)


def softmax_fit_flops(attrs: Dict[str, Any]) -> float:
    """The contractions of one batched softmax fit: ``contractions``
    products of (rows, features) x (features, lanes * classes), the span's
    own count (the solver's schedule). The elementwise work on the
    (rows, lanes, classes) temporaries is not counted: it is bytes, not
    operations of the matrix unit."""
    return float(attrs["contractions"]) * contraction_flops(
        int(attrs["rows"]), int(attrs["features"]),
        int(attrs["lanes"]) * int(attrs["classes"]))
