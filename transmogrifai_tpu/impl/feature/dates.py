"""Date / time feature stages: unit-circle encodings and date-list pivots.

TPU re-design of the reference date stages (reference:
core/.../impl/feature/DateToUnitCircleTransformer.scala:121 — sin/cos circular
encoding per time period; DateMapToUnitCircleVectorizer.scala:134;
DateListVectorizer.scala:309 — SinceFirst/SinceLast/ModeDay/ModeMonth/ModeHour
pivots; TimePeriodTransformer.scala). Epoch-millis int64 host columns are
converted with vectorized numpy datetime64 arithmetic, emitting dense float32
blocks for the device.
"""
from __future__ import annotations

import threading
import time as _time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ...stages.base import SequenceTransformer, UnaryTransformer
from ...table import Column, FeatureTable
from ...types import Date, DateList, DateMap, Integral, OPVector
from ...vector_metadata import (
    NULL_INDICATOR, VectorColumnMetadata, VectorMetadata,
)
from .vectorizers import _VectorModelBase

#: period → (extractor over epoch-ms int64 array, cardinality, offset)
#: matches the reference's TimePeriod enum (joda semantics: Monday=1)
_DAY_MS = 86_400_000
_HOUR_MS = 3_600_000


def _dt_parts(ms: np.ndarray) -> Dict[str, np.ndarray]:
    dt = ms.astype("datetime64[ms]")
    days = dt.astype("datetime64[D]")
    months = dt.astype("datetime64[M]")
    years = dt.astype("datetime64[Y]")
    day_of_month = (days - months.astype("datetime64[D]")).astype(np.int64) + 1
    day_of_year = (days - years.astype("datetime64[D]")).astype(np.int64) + 1
    return {
        "HourOfDay": (ms // _HOUR_MS) % 24,
        "DayOfWeek": ((days.astype(np.int64) + 3) % 7) + 1,  # 1970-01-01 = Thu
        "DayOfMonth": day_of_month,
        "DayOfYear": day_of_year,
        "MonthOfYear": (months.astype(np.int64) % 12) + 1,
        "WeekOfMonth": ((day_of_month - 1) // 7) + 1,
        "WeekOfYear": ((day_of_year - 1) // 7) + 1,
    }


TIME_PERIODS: Dict[str, Dict[str, float]] = {
    "HourOfDay": {"period": 24.0, "offset": 0.0},
    "DayOfWeek": {"period": 7.0, "offset": 1.0},
    "DayOfMonth": {"period": 31.0, "offset": 1.0},
    "DayOfYear": {"period": 366.0, "offset": 1.0},
    "MonthOfYear": {"period": 12.0, "offset": 1.0},
    "WeekOfMonth": {"period": 5.0, "offset": 1.0},
    "WeekOfYear": {"period": 53.0, "offset": 1.0},
}


def time_period_values(ms: np.ndarray, period: str) -> np.ndarray:
    if period not in TIME_PERIODS:
        raise ValueError(
            f"unknown time period '{period}'; one of {sorted(TIME_PERIODS)}")
    return _dt_parts(np.asarray(ms, dtype=np.int64))[period]


def unit_circle(values: np.ndarray, period: str) -> np.ndarray:
    spec = TIME_PERIODS[period]
    radians = 2.0 * np.pi * (values - spec["offset"]) / spec["period"]
    return np.stack([np.sin(radians), np.cos(radians)], axis=1).astype(np.float32)


def unit_circle_block(ms: np.ndarray, periods: Sequence[str]) -> np.ndarray:
    """(n, 2 * len(periods)) float32: ``[sin, cos]`` of every period of the
    epoch-ms column, side by side. Every period is a function of the hour a
    timestamp falls in, so where the column's hours span far fewer values
    than it has rows (a month of trips: 744 hours under millions of rows)
    the encodings are computed once an hour and the rows look theirs up:
    one integer division and one gather a column, the same numbers to the
    bit."""
    def direct(ms):
        return np.concatenate([unit_circle(time_period_values(ms, p), p)
                               for p in periods], axis=1)
    n = len(ms)
    if n == 0 or not periods:
        return np.zeros((n, 2 * len(periods)), dtype=np.float32)
    hours = ms // _HOUR_MS
    lo, hi = int(hours.min()), int(hours.max())
    if hi - lo >= n // 4:
        return direct(ms)
    table = direct(np.arange(lo, hi + 1, dtype=np.int64) * _HOUR_MS)
    hours -= lo
    return np.take(table, hours, axis=0)


class TimePeriodTransformer(UnaryTransformer):
    """Date → Integral time period (reference TimePeriodTransformer.scala)."""

    def __init__(self, period: str = "DayOfWeek", uid=None):
        def fn(v):
            if v is None:
                return None
            return int(time_period_values(np.array([v]), period)[0])
        super().__init__(f"timePeriod{period}", transform_fn=fn,
                         output_type=Integral, input_type=Date, uid=uid)
        self.period = period

    def transform_column(self, table: FeatureTable) -> Column:
        col = table[self.input_features[0].name]
        vals = time_period_values(np.asarray(col.values, dtype=np.int64),
                                  self.period)
        return Column(Integral, vals.astype(np.int64),
                      None if col.mask is None else np.asarray(col.mask))


class TimePeriodListTransformer(UnaryTransformer):
    """DateList → OPVector of per-element time periods (reference
    TimePeriodListTransformer.scala — each timestamp maps to its extracted
    period value). The reference emits ragged per-row vectors; columnar
    arrays are rectangular here, so rows pad/truncate to ``width`` elements
    (pad value -1, never a real period value). With ``width=None`` the
    width is locked by the FIRST batch transformed — its longest list, or 1
    if it is all-empty — and reused for every later batch, so every batch
    emits the same column width; row-wise serving locks from the first ROW
    instead (thread-safe via a class lock). Pass an explicit ``width`` in
    production pipelines where the first batch/row may not be
    representative."""

    #: class-level (hence never serialized) lock guarding the width lock-in
    #: under concurrent serving threads
    _WIDTH_LOCK = threading.Lock()

    def __init__(self, period: str = "DayOfWeek",
                 width: Optional[int] = None, uid=None):
        def fn(v):
            if v is None:
                return None
            arr = np.asarray(list(v), dtype=np.int64)
            vals = [float(x) for x in time_period_values(arr, period)]
            # row path locks the width too (first row seen), so row-wise
            # serving before any columnar batch still emits a fixed width
            width = self._lock_width(len(vals))
            return (vals + [-1.0] * width)[:width]
        super().__init__(f"dateListToTimePeriod{period}", transform_fn=fn,
                         output_type=OPVector, input_type=DateList, uid=uid)
        self.period = period
        self.width = width

    def _lock_width(self, observed: int) -> int:
        if self.width is None:
            with self._WIDTH_LOCK:
                if self.width is None:
                    self.width = max(int(observed), 1)
        return self.width

    def transform_column(self, table: FeatureTable) -> Column:
        col = table[self.input_features[0].name]
        valid = col.valid_mask()
        if self.width is None:
            # lock on first use — even a degenerate all-empty batch, because
            # that batch's (n, 1) output is already emitted and later batches
            # must match it (explicit width exists for that case). Lock from
            # the raw list lengths BEFORE running transform_fn (which itself
            # pads to the locked width)
            lens = [len(col.values[i])
                    if valid[i] and col.values[i] is not None else 0
                    for i in range(len(col))]
            self._lock_width(max(lens, default=1))
        rows = [self.transform_fn(col.values[i]) if valid[i] else None
                for i in range(len(col))]
        width = self.width
        mat = np.full((len(rows), width), -1.0, np.float32)
        for i, r in enumerate(rows):
            if r:
                # rows from transform_fn are already padded once width is
                # locked; re-pad covers only the unlocked first batch
                mat[i, :width] = (r + [-1.0] * width)[:width]
        return Column(OPVector, mat, None)


class TimePeriodMapTransformer(UnaryTransformer):
    """DateMap → IntegralMap of per-key time periods (reference
    TimePeriodMapTransformer.scala)."""

    def __init__(self, period: str = "DayOfWeek", uid=None):
        def fn(v):
            if v is None:
                return None
            return {k: int(time_period_values(
                np.array([t], dtype=np.int64), period)[0])
                for k, t in v.items()}
        from ...types import IntegralMap
        super().__init__(f"dateMapToTimePeriod{period}", transform_fn=fn,
                         output_type=IntegralMap, input_type=DateMap, uid=uid)
        self.period = period


#: reference TransmogrifierDefaults.CircularDateRepresentations
DEFAULT_CIRCULAR_PERIODS = ("HourOfDay", "DayOfWeek", "DayOfMonth", "DayOfYear")


class DateToUnitCircleTransformer(SequenceTransformer):
    """Seq[Date] → OPVector of [sin, cos] per (feature, period) (reference
    DateToUnitCircleTransformer.scala — missing dates map to (0, 0), the
    off-circle marker; Transmogrifier defaults use four circular periods)."""

    output_type = OPVector

    def __init__(self, periods: Sequence[str] = ("HourOfDay",), uid=None):
        super().__init__("toUnitCircle", transform_fn=None,
                         output_type=OPVector, uid=uid)
        self.periods = tuple(periods)

    def transform_column(self, table: FeatureTable) -> Column:
        blocks, meta = [], []
        for f in self.input_features:
            col = table[f.name]
            ms = np.asarray(col.values, dtype=np.int64)
            m = col.valid_mask()
            block = unit_circle_block(ms, self.periods)
            block[~m] = 0.0
            blocks.append(block)
            for period in self.periods:
                meta.extend([
                    VectorColumnMetadata(f.name, f.type_name, f.name, None,
                                         descriptor_value=f"{period}_sin"),
                    VectorColumnMetadata(f.name, f.type_name, f.name, None,
                                         descriptor_value=f"{period}_cos"),
                ])
        vm = VectorMetadata.of(self.get_output().name, meta)
        return Column(OPVector, np.concatenate(blocks, axis=1), None,
                      {"vector_meta": vm})



class DateMapToUnitCircleVectorizer(SequenceTransformer):
    """Seq[DateMap] → OPVector: sin/cos per map key (reference
    DateMapToUnitCircleVectorizer.scala). Key space is taken per batch; for a
    stable key space across train/score pass ``keys`` explicitly."""

    output_type = OPVector

    def __init__(self, period: str = "HourOfDay",
                 keys: Optional[Sequence[str]] = None, uid=None):
        super().__init__("mapToUnitCircle", transform_fn=None,
                         output_type=OPVector, uid=uid)
        self.period = period
        self.keys = list(keys) if keys is not None else None

    def transform_column(self, table: FeatureTable) -> Column:
        n = table.num_rows
        blocks, meta = [], []
        for f in self.input_features:
            col = table[f.name]
            valid = col.valid_mask()
            rows = [col.values[i] if valid[i] and col.values[i] is not None
                    else None for i in range(n)]
            keys = self.keys
            if keys is None:
                keys = sorted({str(k) for r in rows if r for k in r})
            for key in keys:
                ms = np.array([int(r[key]) if r and key in r and r[key] is not None
                               else 0 for r in rows], dtype=np.int64)
                present = np.array([bool(r and key in r and r[key] is not None)
                                    for r in rows])
                block = unit_circle(time_period_values(ms, self.period),
                                    self.period)
                block[~present] = 0.0
                blocks.append(block)
                meta.extend([
                    VectorColumnMetadata(f.name, f.type_name, key, None,
                                         descriptor_value=f"{self.period}_sin"),
                    VectorColumnMetadata(f.name, f.type_name, key, None,
                                         descriptor_value=f"{self.period}_cos"),
                ])
        vm = VectorMetadata.of(self.get_output().name, meta)
        mat = (np.concatenate(blocks, axis=1) if blocks
               else np.zeros((n, 0), dtype=np.float32))
        return Column(OPVector, mat, None, {"vector_meta": vm})



#: DateList pivot kinds (reference DateListPivot enum)
DATE_LIST_PIVOTS = ("SinceFirst", "SinceLast", "ModeDay", "ModeMonth", "ModeHour")


class DateListVectorizer(SequenceTransformer):
    """Seq[DateList] → OPVector with pivot encodings (reference
    DateListVectorizer.scala:309):

    * SinceFirst / SinceLast — days between ``reference_date`` and the
      first/last timestamp (+ null indicator);
    * ModeDay — one-hot(7) of the modal day-of-week;
    * ModeMonth — one-hot(12) of the modal month;
    * ModeHour — one-hot(24) of the modal hour.
    """

    output_type = OPVector

    def __init__(self, pivot: str = "SinceLast",
                 reference_date_ms: Optional[int] = None,
                 track_nulls: bool = True, uid=None):
        super().__init__(f"dateList{pivot}", transform_fn=None,
                         output_type=OPVector, uid=uid)
        if pivot not in DATE_LIST_PIVOTS:
            raise ValueError(f"pivot must be one of {DATE_LIST_PIVOTS}")
        self.pivot = pivot
        # pinned at construction so train/score agree (determinism; the
        # reference defaults to TransmogrifierDefaults.ReferenceDate "now")
        self.reference_date_ms = (int(_time.time() * 1000)
                                  if reference_date_ms is None
                                  else int(reference_date_ms))
        self.track_nulls = track_nulls

    _MODE_SPECS = {"ModeDay": ("DayOfWeek", 7, 1),
                   "ModeMonth": ("MonthOfYear", 12, 1),
                   "ModeHour": ("HourOfDay", 24, 0)}

    def transform_column(self, table: FeatureTable) -> Column:
        n = table.num_rows
        blocks, meta = [], []
        for f in self.input_features:
            col = table[f.name]
            valid = col.valid_mask()
            lists = [col.values[i] if valid[i] else None for i in range(n)]
            if self.pivot in ("SinceFirst", "SinceLast"):
                take = min if self.pivot == "SinceFirst" else max
                days = np.zeros(n, dtype=np.float32)
                nulls = np.zeros(n, dtype=np.float32)
                for i, lst in enumerate(lists):
                    if not lst:
                        nulls[i] = 1.0
                        continue
                    days[i] = (self.reference_date_ms - take(lst)) / _DAY_MS
                cols = [days]
                meta.append(VectorColumnMetadata(
                    f.name, f.type_name, f.name, None,
                    descriptor_value=self.pivot))
                if self.track_nulls:
                    cols.append(nulls)
                    meta.append(VectorColumnMetadata(
                        f.name, f.type_name, f.name, NULL_INDICATOR))
                blocks.append(np.stack(cols, axis=1))
            else:
                period, card, offset = self._MODE_SPECS[self.pivot]
                block = np.zeros((n, card), dtype=np.float32)
                for i, lst in enumerate(lists):
                    if not lst:
                        continue
                    vals = time_period_values(
                        np.asarray(lst, dtype=np.int64), period)
                    vv, cc = np.unique(vals, return_counts=True)
                    mode = int(vv[np.argmax(cc)])  # ties → smallest value
                    block[i, mode - offset] = 1.0
                blocks.append(block)
                meta.extend([VectorColumnMetadata(
                    f.name, f.type_name, f.name, f"{self.pivot}_{j + offset}")
                    for j in range(card)])
        vm = VectorMetadata.of(self.get_output().name, meta)
        return Column(OPVector, np.concatenate(blocks, axis=1), None,
                      {"vector_meta": vm})



# circular import avoidance: FeatureTable already imported at module top
