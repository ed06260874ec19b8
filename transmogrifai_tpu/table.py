"""FeatureTable — the columnar, device-resident replacement for the reference's
Spark DataFrame substrate.

Where the reference materializes a row-oriented ``DataFrame`` and runs stages as
row lambdas inside Catalyst (reference: readers/.../DataReader.scala:173,
core/.../utils/stages/FitStagesUtil.scala:96-119), the TPU build keeps a dict of
*columns*. Numeric columns live as device arrays (values + validity mask) that
jitted kernels consume directly and that shard over the mesh row axis; string /
list / map columns stay host-side (numpy object arrays) until a vectorizer
encodes them into device arrays — strings never cross the host→device boundary.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, Iterator, List, Mapping, Optional, Sequence, Tuple, Type

import numpy as np

from .types import FeatureType

#: column kinds whose values are numeric arrays eligible for device residency
#: (integral/date stay host-side int64 — TPU x64 is off and vectorizers emit
#: float32 blocks from them anyway)
DEVICE_KINDS = frozenset({"real", "binary", "vector", "prediction"})
#: column kinds kept host-side (object arrays / int64) until vectorized
HOST_KINDS = frozenset({"text", "text_list", "date_list", "geolocation",
                        "multipicklist", "map", "date", "integral"})


def _np(values) -> np.ndarray:
    return np.asarray(values)


@dataclass(frozen=True)
class Column:
    """One feature column.

    values:
      * kind 'real'/'binary': float32 (n,) — invalid slots hold 0.0
      * kind 'integral': int32 (n,) — invalid slots hold 0
      * kind 'date': int64 host array (n,) (epoch millis exceed int32/float32)
      * kind 'vector': float32 (n, d) device array, no mask
      * kind 'prediction': float32 (n, k) + ``keys`` metadata entry
      * kind 'text'/'map'/lists: numpy object array (n,)
    mask: bool (n,) validity mask; None means all-valid.
    metadata: free-form provenance (e.g. vector metadata under 'vector_meta').
    """
    feature_type: Type[FeatureType]
    values: Any
    mask: Optional[Any] = None
    metadata: Mapping[str, Any] = field(default_factory=dict)

    @property
    def kind(self) -> str:
        return self.feature_type.column_kind

    def __len__(self) -> int:
        return int(self.values.shape[0])

    @property
    def width(self) -> int:
        return int(self.values.shape[1]) if self.values.ndim > 1 else 1

    def valid_mask(self) -> np.ndarray:
        if self.mask is None:
            return np.ones(len(self), dtype=bool)
        return np.asarray(self.mask)

    def with_metadata(self, **kv) -> "Column":
        md = dict(self.metadata)
        md.update(kv)
        return replace(self, metadata=md)

    def to_device(self) -> "Column":
        """Move numeric storage onto the default device as jax arrays."""
        if self.kind not in DEVICE_KINDS:
            return self
        import jax.numpy as jnp
        vals = jnp.asarray(self.values)
        mask = None if self.mask is None else jnp.asarray(self.mask)
        return replace(self, values=vals, mask=mask)

    def to_host(self) -> "Column":
        vals = np.asarray(self.values)
        mask = None if self.mask is None else np.asarray(self.mask)
        return replace(self, values=vals, mask=mask)

    def take(self, idx: np.ndarray) -> "Column":
        vals = self.values[idx]
        mask = None if self.mask is None else self.mask[idx]
        return replace(self, values=vals, mask=mask)

    # -- constructors --------------------------------------------------------
    @staticmethod
    def of_values(feature_type: Type[FeatureType], raw: Sequence[Any]) -> "Column":
        """Build a column from raw python values (None/NaN = missing)."""
        kind = feature_type.column_kind
        n = len(raw)
        if kind in ("real", "binary", "integral", "date"):
            missing = [_is_missing_scalar(v) for v in raw]
            mask = np.array([not m for m in missing], dtype=bool)
            if kind == "real":
                vals = np.array([0.0 if m else float(v)
                                 for v, m in zip(raw, missing)], dtype=np.float32)
            elif kind == "binary":
                vals = np.array([0.0 if m else float(bool(v))
                                 for v, m in zip(raw, missing)], dtype=np.float32)
            else:  # integral/date: reference semantics are Long → host int64
                vals = np.array([0 if m else int(v)
                                 for v, m in zip(raw, missing)], dtype=np.int64)
            return Column(feature_type, vals, mask)
        if kind == "vector":
            vals = np.stack([np.asarray([] if v is None else v, dtype=np.float32)
                             for v in raw]) if n else np.zeros((0, 0), dtype=np.float32)
            return Column(feature_type, vals, None)
        if kind == "prediction":
            keys = sorted({k for d in raw if d is not None for k in d})
            vals = np.array([[float(d.get(k, 0.0)) for k in keys]
                             if d is not None else [0.0] * len(keys)
                             for d in raw], dtype=np.float32).reshape(n, len(keys))
            return Column(feature_type, vals, None, {"keys": tuple(keys)})
        # host kinds
        arr = np.empty(n, dtype=object)
        for i, v in enumerate(raw):
            arr[i] = v
        mask = np.array([not _is_missing(v) for v in raw], dtype=bool)
        return Column(feature_type, arr, mask)


def column_of_scalars(feature_type: Type[FeatureType],
                      raw: Sequence[Any]) -> Optional[Column]:
    """Vectorized dual of ``Column.of_values`` for numeric scalar kinds:
    one ``np.asarray`` sweep instead of a python loop calling
    ``float()``/``int()`` per cell — the serve-time request→table hot path
    (local/scoring.serve_table_builder). Returns None whenever the batch
    is not homogeneous numeric (a None, a string, a FeatureType wrapper) —
    the caller falls back to
    ``of_values``, so semantics are byte-identical by construction:
    NaN = missing, invalid slots hold 0, binary truth-tests, integral
    truncation all match the per-cell path."""
    kind = feature_type.column_kind
    if kind not in ("real", "binary", "integral", "date") or not len(raw):
        return None
    try:
        vals = np.asarray(raw, dtype=np.float64)
    except (TypeError, ValueError):
        return None
    if vals.shape != (len(raw),):
        return None
    mask = ~np.isnan(vals)
    if kind == "real":
        return Column(feature_type,
                      np.where(mask, vals, 0.0).astype(np.float32), mask)
    if kind == "binary":
        return Column(feature_type,
                      (np.where(mask, vals, 0.0) != 0.0).astype(np.float32),
                      mask)
    # integral/date → host int64 (reference Long semantics); float cells
    # truncate toward zero exactly like int(v)
    if kind == "integral" or kind == "date":
        with np.errstate(invalid="ignore"):
            ints = np.where(mask, vals, 0.0).astype(np.int64)
        return Column(feature_type, ints, mask)
    return None


def _is_missing_scalar(v: Any) -> bool:
    if v is None:
        return True
    if isinstance(v, float) and np.isnan(v):
        return True
    return False


def _is_missing(v: Any) -> bool:
    if v is None:
        return True
    if isinstance(v, float) and np.isnan(v):
        return True
    if isinstance(v, (list, set, dict, tuple)) and len(v) == 0:
        return True
    return False


class FeatureTable:
    """Immutable-ish columnar table: name → Column, plus an optional key column.

    The TPU-native analog of the materialized raw DataFrame produced by
    ``DataReader.generateDataFrame`` (reference DataReader.scala:173-197).
    """

    KEY = "key"

    def __init__(self, columns: Dict[str, Column], num_rows: int,
                 key: Optional[np.ndarray] = None):
        self._columns = dict(columns)
        self.num_rows = num_rows
        self.key = key
        for name, col in self._columns.items():
            if len(col) != num_rows:
                raise ValueError(
                    f"column '{name}' has {len(col)} rows, table has {num_rows}")

    # -- access --------------------------------------------------------------
    def __contains__(self, name: str) -> bool:
        return name in self._columns

    def __getitem__(self, name: str) -> Column:
        return self._columns[name]

    def get(self, name: str) -> Optional[Column]:
        return self._columns.get(name)

    @property
    def column_names(self) -> List[str]:
        return list(self._columns)

    def __len__(self) -> int:
        return self.num_rows

    # -- functional updates --------------------------------------------------
    def with_column(self, name: str, col: Column) -> "FeatureTable":
        cols = dict(self._columns)
        cols[name] = col
        return FeatureTable(cols, self.num_rows, self.key)

    def with_columns(self, new: Mapping[str, Column]) -> "FeatureTable":
        cols = dict(self._columns)
        cols.update(new)
        return FeatureTable(cols, self.num_rows, self.key)

    def select(self, names: Sequence[str]) -> "FeatureTable":
        return FeatureTable({n: self._columns[n] for n in names}, self.num_rows, self.key)

    def drop(self, names: Sequence[str]) -> "FeatureTable":
        gone = set(names)
        return FeatureTable(
            {n: c for n, c in self._columns.items() if n not in gone},
            self.num_rows, self.key)

    def take(self, idx: np.ndarray) -> "FeatureTable":
        idx = np.asarray(idx)
        key = None if self.key is None else self.key[idx]
        return FeatureTable({n: c.take(idx) for n, c in self._columns.items()},
                            int(idx.shape[0]), key)

    def to_device(self) -> "FeatureTable":
        """Move every device-kind column onto the default device with O(1)
        host→device transfers: values pack into one stacked block per dtype
        and masks into one bool block, transfer once, and split back into
        per-column device views (cheap on-device slices). The per-column
        ``Column.to_device`` path costs one transfer per column — O(columns)
        where this is O(dtypes).
        """
        import jax.numpy as jnp

        from .observability import metrics as _obs_metrics
        todo = [(n, c) for n, c in self._columns.items()
                if c.kind in DEVICE_KINDS
                and isinstance(c.values, np.ndarray)]
        if not todo:
            return FeatureTable(
                {n: c.to_device() for n, c in self._columns.items()},
                self.num_rows, self.key)
        by_dtype: Dict[str, List[Tuple[str, np.ndarray]]] = {}
        masked: List[Tuple[str, np.ndarray]] = []
        for n, c in todo:
            by_dtype.setdefault(str(c.values.dtype), []).append(
                (n, np.ascontiguousarray(c.values).reshape(-1)))
            if c.mask is not None:
                masked.append((n, np.asarray(c.mask)))
        transfers = 0
        nbytes = 0
        flat_dev: Dict[str, Any] = {}
        for dt, parts in by_dtype.items():
            host = (np.concatenate([v for _, v in parts])
                    if len(parts) > 1 else parts[0][1])
            flat_dev[dt] = jnp.asarray(host)
            transfers += 1
            nbytes += host.nbytes
        mask_dev = None
        if masked:
            mhost = (np.concatenate([m for _, m in masked])
                     if len(masked) > 1 else masked[0][1])
            mask_dev = jnp.asarray(mhost)
            transfers += 1
            nbytes += mhost.nbytes
        _obs_metrics.inc_counter(
            "tg_device_transfer_total", float(transfers),
            help="host→device uploads (packed: see docs/plan.md)")
        _obs_metrics.inc_counter(
            "tg_transfer_bytes_total", float(nbytes), direction="h2d",
            help="bytes moved across the host<->device link")
        offs = {dt: 0 for dt in flat_dev}
        moff = 0
        mask_at: Dict[str, Any] = {}
        for n, m in masked:
            mask_at[n] = mask_dev[moff:moff + m.shape[0]]
            moff += m.shape[0]
        cols: Dict[str, Column] = {}
        for n, c in self._columns.items():
            if c.kind not in DEVICE_KINDS or not isinstance(c.values, np.ndarray):
                cols[n] = c.to_device()
                continue
            dt = str(c.values.dtype)
            size = int(c.values.size)
            vals = flat_dev[dt][offs[dt]:offs[dt] + size].reshape(
                c.values.shape)
            offs[dt] += size
            cols[n] = replace(c, values=vals, mask=mask_at.get(n))
        return FeatureTable(cols, self.num_rows, self.key)

    # -- row view (local scoring / tests) ------------------------------------
    def row(self, i: int) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for name, col in self._columns.items():
            valid = col.mask is None or bool(np.asarray(col.mask)[i])
            if not valid:
                out[name] = None
            else:
                v = np.asarray(col.values)[i]
                out[name] = v.tolist() if isinstance(v, np.ndarray) else (
                    v.item() if isinstance(v, np.generic) else v)
        return out

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for i in range(self.num_rows):
            yield self.row(i)

    # -- constructors --------------------------------------------------------
    @staticmethod
    def from_columns(data: Mapping[str, Tuple[Type[FeatureType], Sequence[Any]]],
                     key: Optional[Sequence[str]] = None) -> "FeatureTable":
        cols = {name: Column.of_values(ft, vals) for name, (ft, vals) in data.items()}
        n = len(next(iter(cols.values()))) if cols else 0
        karr = None if key is None else np.asarray(key, dtype=object)
        return FeatureTable(cols, n, karr)
