"""Byte counts of the program's memory-bound kernels, from shapes: what the
algorithm must read, whatever implements it and however the device lays the
rows out. A roofline metric divides these by device seconds and the memory
bandwidth of ``peaks.json``.

Each ``*_bytes`` function that a ``layer_metrics`` file names under
``bytecount`` takes one span's attributes (the program's own: shapes and how
many passes its schedule makes) and returns bytes; it raises ``KeyError``
where an attribute is missing, which a reader takes as nothing to read.
"""
from __future__ import annotations

from typing import Any, Dict


def matrix_pass_bytes(rows: int, features: int, itemsize: int = 4) -> float:
    """One read of a (rows, features) matrix."""
    return float(rows) * float(features) * float(itemsize)


def moment_passes_bytes(attrs: Dict[str, Any]) -> float:
    """The passes over the float32 feature matrix that build a linear or
    generalised-linear fit's moments: ``gramPasses`` reads of (rows,
    features), the span's own count (the solver's schedule). The label, the
    lanes' weights and the small systems are not counted: a lower bound."""
    return float(attrs["gramPasses"]) * matrix_pass_bytes(
        int(attrs["rows"]), int(attrs["features"]))
