"""Feature vectorizers: typed columns → OPVector columns with provenance.

TPU re-design of the reference vectorizer zoo (reference:
core/.../impl/feature/RealVectorizer.scala, IntegralVectorizer.scala,
BinaryVectorizer.scala, OpOneHotVectorizer.scala, SmartTextVectorizer.scala,
OPCollectionHashingVectorizer.scala, TextTokenizer.scala,
VectorsCombiner.scala, TransmogrifierDefaults Transmogrifier.scala:52-90).

Execution split: statistics and string handling (vocab counts, tokenizing,
hashing) run host-side in vectorized numpy — they are string work the TPU
cannot express — and emit dense float32 blocks; everything downstream (models,
stats, scoring) consumes the resulting device arrays. From
``_DEVICE_BLOCK_MIN_ROWS`` rows on, the pivot hands the device each row's
position in its column's block and the device writes the dense block, the
Real / Integral fills send each input column up by itself (values, and the
validity mask where there is one) and the device fills them into their
block, and the combiner joins its inputs there: no host array of (rows x
derived columns) is made. Null semantics match the reference: mean/mode
fill + a tracked null-indicator column per feature.
"""
from __future__ import annotations

import weakref
import zlib
from collections import Counter
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

from ...features import Feature
from ...observability.trace import span as _obs_span, tracer as _obs_tracer
from ...parallel.distributed import _count_transfer_bytes
from ...parallel.sharded import place_rows, row_sharding
from ...stages.base import Estimator, SequenceTransformer, Transformer, UnaryTransformer
from ...table import Column, FeatureTable
from ...types import (
    Binary, FeatureType, Integral, MultiPickList, OPVector, Real, RealNN, Text,
    TextList,
)
from ...vector_metadata import (
    NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMetadata, VectorMetadata,
)


class TransmogrifierDefaults:
    """Default knobs (reference Transmogrifier.scala:52-90)."""
    TopK = 20
    MinSupport = 10
    FillValue = 0.0
    BinaryFillValue = False
    NumHashes = 512
    MaxNumOfFeatures = 16384
    MaxCardinality = 30          # SmartTextVectorizer pivot-vs-hash cutoff
    MinTokenLength = 1
    TrackNulls = True
    FillWithMean = True
    FillWithMode = True


def _meta_cols(feature: Feature, names_vals: Sequence[Tuple[Optional[str], Optional[str]]]
               ) -> List[VectorColumnMetadata]:
    return [VectorColumnMetadata(
        parent_feature_name=feature.name,
        parent_feature_type=feature.type_name,
        grouping=grouping, indicator_value=indicator)
        for grouping, indicator in names_vals]


class _VectorModelBase(Transformer):
    """Shared: produce an OPVector Column with attached VectorMetadata."""

    output_type = OPVector

    def _emit(self, mat, meta_cols: List[VectorColumnMetadata]) -> Column:
        """``mat`` as the stage's column: a host matrix as contiguous
        float32, a block the device wrote as it is."""
        vm = VectorMetadata.of(self.get_output().name, meta_cols)
        if not isinstance(mat, jax.Array):
            mat = np.ascontiguousarray(mat, dtype=np.float32)
        return Column(OPVector, mat, None, {"vector_meta": vm})

    def transform_row(self, row: Dict[str, Any]) -> Any:
        one = FeatureTable(
            {f.name: Column.of_values(f.feature_type, [row.get(f.name)])
             for f in self.input_features}, 1)
        return np.asarray(self.transform_column(one).values)[0].tolist()


# ---------------------------------------------------------------------------
# Numeric vectorizers
# ---------------------------------------------------------------------------

class RealVectorizer(Estimator):
    """Seq[Real] → OPVector: mean-fill + null indicators (reference
    RealVectorizer.scala:121 — fills with mean, tracks nulls)."""

    output_type = OPVector

    def __init__(self, fill_with_mean: bool = TransmogrifierDefaults.FillWithMean,
                 fill_value: float = TransmogrifierDefaults.FillValue,
                 track_nulls: bool = TransmogrifierDefaults.TrackNulls, uid=None):
        super().__init__("vecReal", uid)
        self.fill_with_mean = fill_with_mean
        self.fill_value = fill_value
        self.track_nulls = track_nulls
        self.mesh = None

    def set_mesh(self, mesh) -> "RealVectorizer":
        """Compute the mean fills over rows sharded on the mesh's 'data'
        axis (reference: per-partition aggregation of the fill statistics,
        SURVEY §2.10 P1)."""
        self.mesh = mesh
        return self

    def fit(self, table: FeatureTable) -> Transformer:
        mesh = getattr(self, "mesh", None)
        if self.fill_with_mean and mesh is not None and self.input_features:
            from ...parallel.sharded import sharded_col_stats
            with _obs_span("realvec.stack",
                           columns=len(self.input_features)) as step:
                cols = [table[f.name] for f in self.input_features]
                mask = np.stack([c.valid_mask() for c in cols], axis=1)
                vals64 = [np.asarray(c.values, dtype=np.float64).reshape(-1)
                          for c in cols]
                # anchor each column at a coarse host mean so the f32 device
                # reduction works on deviations (error ~ eps·std, matching
                # the f64 host path's fills to float precision even for
                # columns with mean >> std); invalid slots are zeroed, inf
                # still propagates. STRIDED sample — a head sample would
                # misanchor sorted/trending columns (ids, timestamps)
                def _anchor(v, m):
                    mv = v[m]
                    if not len(mv):
                        return 0.0
                    return mv[::max(1, len(mv) // 1024)][:1024].mean()
                anchors = np.array(
                    [_anchor(v, mask[:, i]) for i, v in enumerate(vals64)])
                X = np.stack(
                    [np.where(mask[:, i], v - anchors[i], 0.0)
                     for i, v in enumerate(vals64)],
                    axis=1).astype(np.float32)
                step.set_attr(bytes=int(X.nbytes))
            # the upload, the sharded moments and the fetch of count / mean
            with _obs_span("realvec.stats", path="mesh"):
                st = sharded_col_stats(X, mask, mesh)
                cnt = np.asarray(st.count)
                mean = np.asarray(st.mean)
                fills = [float(anchors[i] + mean[i]) if cnt[i] > 0
                         else self.fill_value for i in range(len(cols))]
        else:
            # the host moments: a float64 cast and a masked mean a column
            with _obs_span("realvec.stats", path="host"):
                fills = []
                for f in self.input_features:
                    col = table[f.name]
                    vals = np.asarray(col.values, dtype=np.float64)
                    m = col.valid_mask()
                    if self.fill_with_mean:
                        fills.append(float(vals[m].mean()) if m.any()
                                     else self.fill_value)
                    else:
                        fills.append(self.fill_value)
        model = RealVectorizerModel(fills=fills, track_nulls=self.track_nulls)
        model.mesh = mesh                            # run-time, never saved
        return self._finalize_model(model)

    # -- streaming fit (OpWorkflow.train(stream=...), docs/streaming.md) -----
    def fit_streaming_prep(self, run):
        """Single-pass prep spec ``(pass_id, fold, extract, finish)`` —
        the trainer fuses independent specs from one DAG layer into one
        chunk sweep (streaming/trainer.py). ``None`` when constant fills
        need no pass at all."""
        if not self.fill_with_mean:
            return None
        from ...streaming.folds import ColStatsFold
        k = len(self.input_features)
        fold = ColStatsFold(k)

        def extract(table):
            cols = [table[f.name] for f in self.input_features]
            X = np.stack([np.asarray(c.values, dtype=np.float64).reshape(-1)
                          for c in cols], axis=1)
            mask = np.stack([c.valid_mask() for c in cols], axis=1)
            return X, mask

        def finish(state) -> Transformer:
            res = fold.finalize(state)
            fills = [float(res.mean[i]) if res.count[i] > 0
                     else self.fill_value for i in range(k)]
            model = RealVectorizerModel(fills=fills,
                                        track_nulls=self.track_nulls)
            return self._finalize_model(model)

        return "fills", fold, extract, finish

    def fit_streaming(self, run) -> Transformer:
        """Mean fills as one chunked col-stats fold: per-column (count, Σx)
        accumulate in exact f64 exactly like the in-core f64 host path, so
        the streamed fills agree with in-core fills to the last float
        rounding of the identical sum/count division."""
        spec = self.fit_streaming_prep(run)
        if spec is None:
            model = RealVectorizerModel(
                fills=[self.fill_value] * len(self.input_features),
                track_nulls=self.track_nulls)
            return self._finalize_model(model)
        pass_id, fold, extract, finish = spec
        return finish(run.fold(pass_id, fold, extract))


def _fill_blocks(values, masks, fills, track_nulls):
    """The fill and null-track arithmetic, stated once: each column's
    ``where(mask, value, fill)`` and, with ``track_nulls``, ``~mask`` as
    float32 beside it, as one ``(rows, columns)`` float32 block. A mask of
    None is a column without nulls; a fill may be a number or a traced
    scalar."""
    blocks = []
    for vals, mask, fill in zip(values, masks, fills):
        vals = vals.reshape(-1).astype(jnp.float32)
        m = jnp.ones(vals.shape, bool) if mask is None else mask
        blocks.append(jnp.where(m, vals, jnp.float32(fill)))
        if track_nulls:
            blocks.append((~m).astype(jnp.float32))
    return jnp.stack(blocks, axis=1)


def _device_fill_blocks(input_features, fills, track_nulls, env):
    """Shared pure-jax fill+null-track dual used by the fused serve program
    (local/scoring.compiled_score_function): env maps input feature name →
    (values, mask-or-None) jnp arrays; ``fills`` yields one fill per input."""
    values, masks = zip(*(env[f.name] for f in input_features))
    return _fill_blocks(values, masks, fills, track_nulls), None


@partial(jax.jit, static_argnames=("track_nulls", "mesh"))
def _real_block(values, masks, fills, track_nulls: bool, mesh):
    """Each column's (n,) float32 values and (n,) bool mask (None where the
    column has none) → the (n, columns) float32 block of ``_fill_blocks``.
    ``fills`` is a (k,) float32 ARGUMENT: a new fit on the same table finds
    the program. Rows are independent: under a mesh every chip writes its
    own."""
    out = _fill_blocks(values, masks, fills, track_nulls)
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(out, row_sharding(mesh, 2))
    return out


class RealVectorizerModel(_VectorModelBase):
    def __init__(self, fills: List[float], track_nulls: bool, uid=None):
        super().__init__("vecReal", uid)
        self.fills = fills
        self.track_nulls = track_nulls
        self.mesh = None

    def device_columnar(self, env):
        return _device_fill_blocks(self.input_features, self.fills,
                                   self.track_nulls, env)

    def transform_column(self, table: FeatureTable) -> Column:
        """From ``_DEVICE_BLOCK_MIN_ROWS`` rows on each input column goes up
        by itself and ``_real_block`` fills the block on the device (under
        the mesh as shards of rows); a smaller table is filled and stacked
        on the host."""
        n = table.num_rows
        cols = [table[f.name] for f in self.input_features]
        meta = []
        for f in self.input_features:
            meta.extend(_meta_cols(
                f, [(f.name, None), (f.name, NULL_INDICATOR)]
                if self.track_nulls else [(f.name, None)]))
        if n >= _DEVICE_BLOCK_MIN_ROWS:
            mesh = _rows_mesh(getattr(self, "mesh", None), n)
            # a cast where the column is not float32, then the launches of
            # the columns' uploads: values, and masks where there are any
            with _obs_span("realvec.fill", path="device",
                           columns=len(cols)) as step:
                values = tuple(_upload(
                    np.asarray(c.values, dtype=np.float32).reshape(-1),
                    mesh, "realvec.upload") for c in cols)
                masks = tuple(None if c.mask is None else _upload(
                    c.valid_mask().reshape(-1), mesh, "realvec.upload")
                    for c in cols)
                step.set_attr(bytes=sum(
                    int(a.nbytes) for a in values + masks if a is not None))
            # the launch of the one program that writes the block
            with _obs_span("realvec.stack", path="device",
                           columns=len(meta)) as step:
                out = self._emit(_real_block(
                    values, masks, np.asarray(self.fills, dtype=np.float32),
                    track_nulls=bool(self.track_nulls), mesh=mesh), meta)
                step.set_attr(bytes=int(out.values.nbytes))
            return out
        blocks = []
        # a cast, a fill and a null indicator a column, on the host
        with _obs_span("realvec.fill", path="host") as step:
            for col, fill in zip(cols, self.fills):
                vals = np.asarray(col.values, dtype=np.float32).reshape(-1)
                m = col.valid_mask()
                blocks.append(np.where(m, vals, np.float32(fill)))
                if self.track_nulls:
                    blocks.append((~m).astype(np.float32))
            step.set_attr(bytes=sum(int(b.nbytes) for b in blocks))
        # the (rows, columns) block the combiner reads
        with _obs_span("realvec.stack", path="host",
                       columns=len(blocks)) as step:
            out = self._emit(np.stack(blocks, axis=1), meta)
            step.set_attr(bytes=int(out.values.nbytes))
        return out


class IntegralVectorizer(Estimator):
    """Seq[Integral] → OPVector: mode-fill + null indicators (reference
    IntegralVectorizer.scala — fills with mode)."""

    output_type = OPVector

    def __init__(self, fill_with_mode: bool = TransmogrifierDefaults.FillWithMode,
                 fill_value: int = 0,
                 track_nulls: bool = TransmogrifierDefaults.TrackNulls, uid=None):
        super().__init__("vecIntegral", uid)
        self.fill_with_mode = fill_with_mode
        self.fill_value = fill_value
        self.track_nulls = track_nulls
        self.mesh = None

    def set_mesh(self, mesh) -> "IntegralVectorizer":
        """The fitted model writes its block with the rows sharded over the
        mesh's 'data' axis (SURVEY §2.10 P1)."""
        self.mesh = mesh
        return self

    def fit(self, table: FeatureTable) -> Transformer:
        fills = []
        for f in self.input_features:
            col = table[f.name]
            vals = np.asarray(col.values).reshape(-1)
            m = col.valid_mask()
            if self.fill_with_mode and m.any():
                vv, cc = np.unique(vals[m], return_counts=True)
                # ties → smallest value (deterministic, matches modeFn min)
                fills.append(float(vv[np.argmax(cc)]))
            else:
                fills.append(float(self.fill_value))
        model = RealVectorizerModel(fills=fills, track_nulls=self.track_nulls)
        model.operation_name = "vecIntegral"
        model.mesh = getattr(self, "mesh", None)     # run-time, never saved
        return self._finalize_model(model)


class BinaryVectorizer(SequenceTransformer):
    """Seq[Binary] → OPVector: false-fill + null indicator (reference
    BinaryVectorizer.scala)."""

    output_type = OPVector

    def __init__(self, fill_value: bool = TransmogrifierDefaults.BinaryFillValue,
                 track_nulls: bool = TransmogrifierDefaults.TrackNulls, uid=None):
        super().__init__("vecBinary", transform_fn=None, output_type=OPVector, uid=uid)
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def device_columnar(self, env):
        fill = float(self.fill_value)
        return _device_fill_blocks(
            self.input_features, (fill for _ in self.input_features),
            self.track_nulls, env)

    def transform_column(self, table: FeatureTable) -> Column:
        blocks, meta = [], []
        for f in self.input_features:
            col = table[f.name]
            vals = np.asarray(col.values, dtype=np.float32).reshape(-1)
            m = col.valid_mask()
            blocks.append(np.where(m, vals, np.float32(float(self.fill_value))))
            meta.extend(_meta_cols(f, [(f.name, None)]))
            if self.track_nulls:
                blocks.append((~m).astype(np.float32))
                meta.extend(_meta_cols(f, [(f.name, NULL_INDICATOR)]))
        vm = VectorMetadata.of(self.get_output().name, meta)
        return Column(OPVector, np.stack(blocks, axis=1).astype(np.float32),
                      None, {"vector_meta": vm})

    def transform_row(self, row: Dict[str, Any]) -> Any:
        one = FeatureTable(
            {f.name: Column.of_values(f.feature_type, [row.get(f.name)])
             for f in self.input_features}, 1)
        return np.asarray(self.transform_column(one).values)[0].tolist()


class RealNNVectorizer(SequenceTransformer):
    """Seq[RealNN] → OPVector passthrough concat (reference RealNNVectorizer)."""

    output_type = OPVector

    def __init__(self, uid=None):
        super().__init__("vecRealNN", transform_fn=None, output_type=OPVector, uid=uid)

    def device_columnar(self, env):
        """Pure-jax dual for the fused serve program (see RealVectorizerModel)."""
        import jax.numpy as jnp
        return jnp.stack(
            [env[f.name][0].reshape(-1).astype(jnp.float32)
             for f in self.input_features], axis=1), None

    def transform_column(self, table: FeatureTable) -> Column:
        blocks, meta = [], []
        for f in self.input_features:
            col = table[f.name]
            blocks.append(np.asarray(col.values, dtype=np.float32).reshape(-1))
            meta.append(VectorColumnMetadata(f.name, f.type_name, f.name, None))
        vm = VectorMetadata.of(self.get_output().name, meta)
        return Column(OPVector, np.stack(blocks, axis=1), None, {"vector_meta": vm})

    def transform_row(self, row: Dict[str, Any]) -> Any:
        return [float(row.get(f.name) or 0.0) for f in self.input_features]


# ---------------------------------------------------------------------------
# Categorical pivot (one-hot) vectorizer
# ---------------------------------------------------------------------------

#: up to this many values a dict comprehension beats the call into pandas
#: (a serving request transforms one row; crossover read at 128-256 values)
_SMALL_HASH_PASS = 128


def _hash_pass(values: np.ndarray) -> Tuple[np.ndarray, List[str]]:
    """``str`` values → (each value's index into the distinct values, the
    distinct values in order of first appearance), one hash-table pass."""
    if len(values) <= _SMALL_HASH_PASS:
        seen: Dict[str, int] = {}
        return np.array([seen.setdefault(v, len(seen)) for v in values],
                        dtype=np.intp), list(seen)
    codes, uniques = pd.factorize(values)
    return codes, list(uniques)


#: rows the count pass looks at before it chooses, as evenly spaced runs of
#: neighbouring rows (a reader that boxes the equal strings of a chunk as one
#: object shares objects inside a chunk, not between two): 0.2 ms a column on
#: the chip's host, a two-hundredth of a value pass over 1 M rows (PERF.md
#: section 6, PR 37)
_SAMPLE_ROWS = 2048
_SAMPLE_RUNS = 4


class _Slots:
    """An object array's slots (the pointers to its values) as a read-only
    ``intp`` array over the array's own buffer, strides as they are: numpy
    refuses ``view`` on an array of references. Rows hold the same pointer
    where they are the same object, and the view keeps the array alive."""

    def __init__(self, arr: np.ndarray):
        face = dict(arr.__array_interface__)
        face.pop("descr", None)
        face.update(typestr=np.dtype(np.intp).str, data=(face["data"][0], True))
        self.base, self.__array_interface__ = arr, face


def _shares_objects(valid: np.ndarray, slots: np.ndarray) -> bool:
    """Whether equal values of the column are the same OBJECT nearly
    everywhere, from the sample: inside its runs at least a sixteenth of the
    rows repeat an object of their run, and of the rows that repeat a value
    at most a fifth fail to repeat an object. A table made by
    ``astype(str)`` or a ``csv`` loop, or free text, has an object a row (no
    row repeats one); grouping rows by object would buy nothing there and
    cost more than the value pass, since a hash table of as many pointers as
    rows lives in no cache (PERF.md section 6, PR 37)."""
    n = len(valid)
    runs = _SAMPLE_RUNS if n > _SAMPLE_ROWS else 1
    width = min(n, _SAMPLE_ROWS) // runs
    same_object = same_value = 0
    for i in range(runs):
        at = (2 * i + 1) * n // (2 * runs) - width // 2     # mid-segment
        same_object += width - len(np.unique(slots[at:at + width]))
        same_value += width - len({str(v) for v in valid[at:at + width]})
    return (16 * same_object >= runs * width
            and 4 * same_value <= 5 * same_object)


def _first_rows(codes: np.ndarray, k: int) -> np.ndarray:
    """The row where each of ``k`` codes, numbered in order of first
    appearance, first appears: where the running maximum of ``codes`` steps
    up. Read by blocks (short ones first) until the last code is met, so a
    column whose levels all turn up early is not read to its end, and only a
    block's rows above the maximum so far are looked at twice."""
    first = np.empty(k, dtype=np.intp)
    top, start = -1, 0
    while top < k - 1 and start < len(codes):
        part = codes[start:start + min(65536, max(4096, start))]
        new = np.flatnonzero(part > top)        # rows of codes not met before
        if len(new):
            run = np.maximum.accumulate(part[new])
            step = new[np.flatnonzero(np.diff(run, prepend=top))]
            first[part[step]] = start + step
            top = int(run[-1])
        start += len(part)
    return first


def _resolve(values: np.ndarray) -> Tuple[np.ndarray, List[str], str]:
    """Values → (each one's index into the distinct values, these as ``str``
    in order of first appearance, ``path``): ``"hashed"`` where every value
    is a ``str`` already and the objects are hashed as they are; anything
    else goes through ``str()`` first (``"str_pass"``), since
    ``1 == True == 1.0`` would merge under the hash what ``str()`` keeps
    apart."""
    hashed = values.dtype == object and pd.api.types.infer_dtype(
        values, skipna=False) in ("string", "empty")
    if hashed:
        sub, uniques = _hash_pass(values)
        levels = [str(u) for u in uniques]
        # a str subclass with a str() of its own: levels that str() merges
        hashed = len(set(levels)) == len(levels)
    if not hashed:
        sub, levels = _hash_pass(
            np.array([str(v) for v in values], dtype=object))
    return sub, levels, "hashed" if hashed else "str_pass"


def _factorize_valid(vals: np.ndarray, m: np.ndarray
                     ) -> Tuple[np.ndarray, Dict[str, int], str, int]:
    """Values of the rows where ``m`` holds → ``(codes, counts, path,
    objects)``: ``counts`` maps each distinct value, as ``str`` and in order
    of first appearance, to its occurrences; ``codes`` (n,) is each valid
    row's position among them and -1 where ``m`` is false; ``path`` says how
    values were resolved (``_resolve``). Rows that are the same object have
    the same value: where the sample shows that equal values mostly are one
    object (``_shares_objects``), the rows are grouped by object first, an
    integer pass over the pointers, and values are resolved once an object,
    ``objects`` of them, not once a row; ``objects`` is 0 where every row's
    value was resolved. No per-row Python on the hashed path."""
    every = bool(m.all())
    valid = vals if every else vals[m]
    objects = 0
    if (len(valid) > _SMALL_HASH_PASS and valid.dtype == object
            and _shares_objects(valid, slots := np.asarray(_Slots(valid)))):
        sub, distinct = pd.factorize(slots)
        objects = len(distinct)
        merge, levels, path = _resolve(valid[_first_rows(sub, objects)])
        if len(levels) < objects:       # objects that resolve to one level
            sub = merge[sub]
    else:
        sub, levels, path = _resolve(valid)
    counts = dict(zip(levels,
                      np.bincount(sub, minlength=len(levels)).tolist()))
    if every:
        return sub, counts, path, objects
    codes = np.full(len(vals), -1, dtype=np.intp)
    codes[m] = sub
    return codes, counts, path, objects


def _top_levels(counts: Dict[str, int], min_support: int, top_k: int
                ) -> List[str]:
    """The pivot's vocabulary: levels seen at least ``min_support`` times,
    by count descending then value ascending, cut at ``top_k``."""
    top = [v for v, c in counts.items() if c >= min_support]
    return sorted(top, key=lambda v: (-counts[v], v))[:top_k]


#: the codes a fit's count pass made of a column, until the transform of the
#: same array takes them: ``id(values) -> (weakref to the values, mask,
#: codes, levels, path, objects)``. ``train()`` fits a pivot and then
#: transforms the table it was fitted on, so the column is hashed once and
#: not twice. Every fit overwrites its column's entry and the first transform
#: of that array removes it, so nothing is carried from one train to the next
#: or to a score; an entry whose array has died goes with it.
_FIT_CODES: Dict[int, Tuple[Any, np.ndarray, np.ndarray, List[str], str,
                           int]] = {}
_FIT_CODES_MAX = 64


def _keep_fit_codes(vals: np.ndarray, m: np.ndarray, codes: np.ndarray,
                    levels: List[str], path: str, objects: int) -> None:
    key = id(vals)
    try:
        ref = weakref.ref(vals, lambda _: _FIT_CODES.pop(key, None))
    except TypeError:
        return
    while len(_FIT_CODES) >= _FIT_CODES_MAX:
        _FIT_CODES.pop(next(iter(_FIT_CODES)), None)
    _FIT_CODES[key] = (ref, m, codes, levels, path, objects)


def _encode_valid(vals: np.ndarray, m: np.ndarray, index: Dict[str, int],
                  track_nulls: bool) -> Tuple[np.ndarray, str, int]:
    """Each row's position inside its pivot block (int32: the vocabulary
    index under ``index``, ``k = len(index)`` for a level not in it and,
    where ``m`` is false, ``k + 1`` if nulls are tracked and -1, no column,
    if not), and the path and the objects ``_factorize_valid`` gave: the
    dictionary is asked once per level, not once per row. The codes are the
    fit's own where this is the array (and the mask) it counted."""
    kept = _FIT_CODES.pop(id(vals), None)
    if kept is not None and kept[0]() is vals and np.array_equal(kept[1], m):
        _, _, codes, levels, path, objects = kept
    else:
        codes, counts, path, objects = _factorize_valid(vals, m)
        levels = list(counts)
    k = len(index)
    # the last entry is the one the null rows' -1 reaches
    lut = np.array([index.get(v, k) for v in levels]
                   + [k + 1 if track_nulls else -1], dtype=np.int32)
    return lut[codes], path, objects


#: rows from which the chip makes the dense blocks: the pivot hands it each
#: column's positions and one program writes the block there, the combiner
#: joins its inputs there; below it the host writes and joins them (PERF.md
#: section 6, PR 35: where positions + dispatch beat block + upload on the
#: chip). An eager stage sees the caller's exact row count and a program
#: compiles once per count, so a stream of small batches of many sizes
#: (serving, micro-batch scoring, ``transform_row``) never meets either.
_DEVICE_BLOCK_MIN_ROWS = 262144


def _upload(host: np.ndarray, mesh, site: str):
    """A host array onto the device, its bytes counted: as shards of rows
    under ``mesh`` (whose data axis the caller saw divide the rows), whole
    on the default device without one."""
    if mesh is not None:
        return place_rows(host, mesh, site=site)
    arr = jnp.asarray(host)
    _count_transfer_bytes(arr, "h2d")
    return arr


def _rows_mesh(mesh, n: int):
    """``mesh`` where its data axis divides ``n`` rows, else None: a table
    that cannot be split evenly stays on one device (padding here would
    change its row count; consumers that need exact shards re-pad with
    masked rows, see ``shard_rows``)."""
    return mesh if mesh is not None and n % mesh.shape["data"] == 0 else None


@partial(jax.jit, static_argnames=("widths", "mesh"))
def _pivot_block(positions, widths: Tuple[int, ...], mesh):
    """Each column's (n,) positions → the (n, sum(widths)) float32 block:
    a one where a row's position meets the column's index, so -1 leaves its
    row empty. Rows are independent: under a mesh every chip writes its
    own."""
    out = jnp.concatenate(
        [(p[:, None] == jnp.arange(w, dtype=p.dtype)[None, :]
          ).astype(jnp.float32) for p, w in zip(positions, widths)], axis=1)
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(out, row_sharding(mesh, 2))
    return out


class OneHotVectorizer(Estimator):
    """Seq[Text-ish] → OPVector: top-K pivot with OTHER + null indicator
    (reference OpOneHotVectorizer.scala / OpTextPivotVectorizer — TopK by
    count with MinSupport, OTHER column, null-indicator column)."""

    output_type = OPVector

    def __init__(self, top_k: int = TransmogrifierDefaults.TopK,
                 min_support: int = TransmogrifierDefaults.MinSupport,
                 track_nulls: bool = TransmogrifierDefaults.TrackNulls, uid=None):
        super().__init__("pivot", uid)
        self.top_k = top_k
        self.min_support = min_support
        self.track_nulls = track_nulls
        self.mesh = None

    def set_mesh(self, mesh) -> "OneHotVectorizer":
        """The fitted model writes its block with the rows sharded over the
        mesh's 'data' axis (SURVEY §2.10 P1)."""
        self.mesh = mesh
        return self

    def fit(self, table: FeatureTable) -> Transformer:
        vocabs: List[List[str]] = []
        for f in self.input_features:
            with _obs_span("onehot.count", cat="train", column=f.name,
                           rows=table.num_rows) as count_span:
                col = table[f.name]
                vals = np.asarray(col.values)
                m = col.valid_mask()
                if col.kind == "multipicklist":
                    cnt = Counter(v for vs, ok in zip(vals, m) if ok
                                  for v in (vs or ()))
                else:
                    codes, cnt, path, objects = _factorize_valid(vals, m)
                    _keep_fit_codes(vals, m, codes, list(cnt), path, objects)
                    count_span.set_attr(path=path, objects=objects)
                vocabs.append(_top_levels(cnt, self.min_support, self.top_k))
                count_span.set_attr(levels=len(cnt))
        model = OneHotVectorizerModel(vocabs=vocabs, track_nulls=self.track_nulls)
        model.mesh = getattr(self, "mesh", None)     # run-time, never saved
        return self._finalize_model(model)


class OneHotVectorizerModel(_VectorModelBase):
    def __init__(self, vocabs: List[List[str]], track_nulls: bool, uid=None):
        super().__init__("pivot", uid)
        self.vocabs = vocabs
        self.track_nulls = track_nulls
        self.mesh = None

    def transform_column(self, table: FeatureTable) -> Column:
        n = table.num_rows
        cols = [table[f.name] for f in self.input_features]
        # the chip makes the block from positions where every column has one
        # per row (a multi-valued column has none) and the table is large
        on_device = n >= _DEVICE_BLOCK_MIN_ROWS and not any(
            c.kind == "multipicklist" for c in cols)
        mesh = _rows_mesh(getattr(self, "mesh", None), n)
        blocks, widths, meta = [], [], []
        for f, vocab, col in zip(self.input_features, self.vocabs, cols):
            vals = np.asarray(col.values)
            m = col.valid_mask()
            k = len(vocab)
            widths.append(k + 1 + (1 if self.track_nulls else 0))
            index = {v: i for i, v in enumerate(vocab)}
            multi = col.kind == "multipicklist"
            # values to positions: one hash pass and a look-up per level (a
            # multi-valued column fills its block as it goes)
            with _obs_span("onehot.encode", column=f.name) as encode_span:
                if multi:
                    block = np.zeros((n, widths[-1]), dtype=np.float32)
                    for i, (vs, ok) in enumerate(zip(vals, m)):
                        if not ok:
                            continue
                        for v in (vs or ()):
                            block[i, index.get(v, k)] = 1.0
                else:
                    pos, path, objects = _encode_valid(vals, m, index,
                                                       self.track_nulls)
                    encode_span.set_attr(path=path, objects=objects)
            # positions to the dense block: on the host, or on their way to
            # the chip (the launch of the upload)
            with _obs_span("onehot.expand", column=f.name,
                           path="device" if on_device else "host"):
                if on_device:
                    block = _upload(pos, mesh, "onehot.upload")
                elif multi:
                    if self.track_nulls:
                        block[~m, k + 1] = 1.0
                else:
                    block = np.zeros((n, widths[-1]), dtype=np.float32)
                    rows = np.flatnonzero(pos >= 0)
                    block[rows, pos[rows]] = 1.0
            blocks.append(block)
            mc = [(f.name, v) for v in vocab] + [(f.name, OTHER_INDICATOR)]
            if self.track_nulls:
                mc.append((f.name, NULL_INDICATOR))
            meta.extend(_meta_cols(f, mc))
        # the blocks joined: on the device path the launch of the one
        # program that writes them
        with _obs_span("onehot.concat") as concat_span:
            out = self._emit(_pivot_block(
                tuple(blocks), widths=tuple(widths), mesh=mesh)
                if on_device else np.concatenate(blocks, axis=1), meta)
            concat_span.set_attr(bytes=int(out.values.nbytes))
        return out


# ---------------------------------------------------------------------------
# Text: tokenizer, hashing, smart vectorizer
# ---------------------------------------------------------------------------

_TOKEN_SPLIT = None


def tokenize_text(s: Optional[str], min_token_length: int = 1) -> List[str]:
    """Lowercase, split on non-alphanumeric (reference TextTokenizer.scala —
    Lucene analyzer approximated host-side; language detection is a later
    stage)."""
    global _TOKEN_SPLIT
    if s is None:
        return []
    if _TOKEN_SPLIT is None:
        import re
        _TOKEN_SPLIT = re.compile(r"[^\w]+", re.UNICODE)
    return [t for t in _TOKEN_SPLIT.split(s.lower()) if len(t) >= min_token_length]


def porter_stem(w: str) -> str:
    """Compact Porter-style English stemmer (the high-coverage rules of
    steps 1-2: plurals, -ed/-ing, common suffixes — the analog of the
    reference's Lucene per-language analyzers with stemming,
    LuceneTextAnalyzer.scala:203; full Porter fidelity is not the goal,
    stable feature collisions for inflected forms are)."""
    if len(w) <= 3:
        return w
    if w.endswith("sses"):
        w = w[:-2]
    elif w.endswith("ies"):
        w = w[:-2]
    elif w.endswith("s") and not w.endswith("ss") and len(w) > 3:
        w = w[:-1]
    for suf, rep in (("ational", "ate"), ("ization", "ize"),
                     ("fulness", "ful"), ("ousness", "ous"),
                     ("iveness", "ive"), ("tional", "tion"),
                     ("biliti", "ble"), ("entli", "ent"),
                     ("ation", "ate"), ("alism", "al"), ("aliti", "al"),
                     ("ness", ""), ("ment", "")):
        if w.endswith(suf) and len(w) - len(suf) >= 3:
            return w[: len(w) - len(suf)] + rep
    if w.endswith("ing") and len(w) > 5:
        w = w[:-3]
        if len(w) >= 3 and w[-1] == w[-2] and w[-1] not in "lsz":
            w = w[:-1]  # running -> run
        return w
    if w.endswith("ed") and len(w) > 4:
        w = w[:-2]
        if len(w) >= 3 and w[-1] == w[-2] and w[-1] not in "lsz":
            w = w[:-1]
        return w
    if w.endswith("ly") and len(w) > 4:
        return w[:-2]
    return w


def _strip_suffixes(w: str, suffixes, min_stem: int = 3) -> str:
    """Longest-match suffix strip with a minimum stem length — the shared
    skeleton of the light per-language stemmers below."""
    for suf, rep in suffixes:
        if w.endswith(suf) and len(w) - len(suf) >= min_stem:
            return w[: len(w) - len(suf)] + rep
    return w


#: light Snowball-style suffix strippers (reference: Lucene ships full
#: per-language Snowball analyzers, LuceneTextAnalyzer.scala:203; as with
#: porter_stem the goal is stable feature collisions for inflected forms,
#: not linguistic fidelity). Ordered longest-first so the longest suffix
#: wins.
_FR_SUFFIXES = [
    ("issements", ""), ("issement", ""), ("atrices", "ateur"),
    ("ateurs", "ateur"), ("ations", "ation"), ("logies", "logie"),
    ("ements", ""), ("amment", ""), ("emment", ""), ("ances", "ance"),
    ("ables", "able"), ("istes", "iste"), ("euses", "eux"),
    ("ments", "ment"), ("ation", "ation"), ("ance", "ance"),
    ("able", "able"), ("iste", "iste"), ("euse", "eux"), ("ités", "ité"),
    ("ement", ""), ("ives", "if"), ("ive", "if"), ("eaux", "eau"),
    ("aux", "al"), ("ité", "ité"), ("er", ""), ("es", ""), ("s", ""),
    ("e", ""),
]
_DE_SUFFIXES = [
    ("ungen", "ung"), ("heiten", "heit"), ("keiten", "keit"),
    ("lichen", "lich"), ("ischen", "isch"), ("erinnen", "er"),
    ("ern", ""), ("ung", "ung"), ("heit", "heit"),
    ("keit", "keit"), ("lich", "lich"), ("isch", "isch"), ("en", ""),
    ("er", ""), ("es", ""), ("em", ""), ("e", ""), ("s", ""), ("n", ""),
]
_ES_SUFFIXES = [
    ("amientos", ""), ("imientos", ""), ("aciones", "ación"),
    ("amiento", ""), ("imiento", ""), ("adoras", "ador"),
    ("adores", "ador"), ("ancias", "ancia"), ("idades", "idad"),
    ("encias", "encia"), ("amente", ""), ("mente", ""), ("ación", "ación"),
    ("adora", "ador"), ("ancia", "ancia"), ("encia", "encia"),
    ("idad", "idad"), ("istas", "ista"), ("ista", "ista"),
    ("ables", "able"), ("ibles", "ible"), ("able", "able"),
    ("ible", "ible"), ("osos", "oso"), ("osas", "oso"), ("osa", "oso"),
    ("oso", "oso"), ("es", ""), ("as", "a"), ("os", "o"), ("s", ""),
]


_IT_SUFFIXES = [
    ("azioni", "azione"), ("amenti", ""), ("imenti", ""),
    ("amento", ""), ("imento", ""), ("azione", "azione"),
    ("atrici", "atore"), ("atrice", "atore"), ("atori", "atore"),
    ("atore", "atore"), ("abili", "abile"), ("ibili", "ibile"),
    ("abile", "abile"), ("ibile", "ibile"), ("mente", ""),
    ("ista", "ista"), ("isti", "ista"), ("iste", "ista"),
    ("anza", "anza"), ("anze", "anza"), ("ità", "ità"),
    ("osi", "oso"), ("ose", "oso"), ("osa", "oso"), ("oso", "oso"),
    ("are", ""), ("ere", ""), ("ire", ""), ("ato", ""), ("ata", ""),
    ("ati", ""), ("ate", ""), ("i", ""), ("e", ""), ("a", ""), ("o", ""),
]
_PT_SUFFIXES = [
    ("amentos", ""), ("imentos", ""), ("adoras", "ador"),
    ("adores", "ador"), ("amento", ""), ("imento", ""),
    ("ações", "ação"), ("idades", "idade"), ("amente", ""),
    ("mente", ""), ("adora", "ador"), ("ação", "ação"),
    ("antes", "ante"), ("ância", "ância"), ("idade", "idade"),
    ("ismos", "ismo"), ("istas", "ista"), ("ismo", "ismo"),
    ("ista", "ista"), ("osos", "oso"), ("osas", "oso"), ("osa", "oso"),
    ("oso", "oso"), ("ivas", "ivo"), ("ivos", "ivo"), ("iva", "ivo"),
    ("ivo", "ivo"), ("ões", "ão"), ("ar", ""), ("er", ""), ("ir", ""),
    ("es", ""), ("as", "a"), ("os", "o"), ("s", ""),
]
_NL_SUFFIXES = [
    ("heden", "heid"), ("elijke", "elijk"), ("elijk", "elijk"),
    ("ingen", "ing"), ("aren", "aar"), ("eren", ""), ("ende", ""),
    ("tjes", ""), ("ing", "ing"), ("aar", "aar"), ("end", ""),
    ("ster", ""), ("je", ""), ("en", ""), ("er", ""), ("es", ""),
    ("s", ""), ("e", ""),
]
#: Russian: strip reflexive particle first, then the longest
#: verb/adjective/noun ending (RSLP-style ordering, Cyrillic)
_RU_REFLEXIVE = ("ся", "сь")
_RU_SUFFIXES = [
    ("ировать", ""), ("ованный", ""), ("ейший", ""),
    ("ениями", "ение"), ("ениях", "ение"),
    ("ениям", "ение"), ("ением", "ение"), ("ости", "ость"),
    ("остью", "ость"), ("ение", "ение"), ("ения", "ение"),
    ("ении", "ение"), ("ством", "ство"), ("ство", "ство"),
    ("ывать", ""), ("ивать", ""), ("овать", ""), ("аться", ""),
    ("иться", ""), ("ешься", ""), ("ется", ""), ("ители", "итель"),
    ("итель", "итель"), ("ами", ""), ("ями", ""), ("ого", ""),
    ("его", ""), ("ому", ""), ("ему", ""), ("ыми", ""), ("ими", ""),
    ("ая", ""), ("яя", ""), ("ой", ""), ("ый", ""), ("ий", ""),
    ("ем", ""), ("им", ""), ("ом", ""), ("ах", ""), ("ях", ""),
    ("ует", ""), ("ешь", ""), ("ете", ""), ("ает", ""), ("яет", ""),
    ("ить", ""), ("ать", ""),
    ("ять", ""), ("еть", ""), ("ал", ""), ("ил", ""), ("ыл", ""),
    ("ла", ""), ("ло", ""), ("ли", ""), ("ов", ""), ("ев", ""),
    ("ей", ""), ("ам", ""), ("ям", ""), ("ы", ""), ("и", ""),
    ("а", ""), ("я", ""), ("о", ""), ("е", ""), ("у", ""), ("ю", ""),
    ("ь", ""),
]


def french_stem(w: str) -> str:
    return _strip_suffixes(w, _FR_SUFFIXES) if len(w) > 4 else w


def german_stem(w: str) -> str:
    return _strip_suffixes(w, _DE_SUFFIXES, min_stem=4) if len(w) > 4 else w


def spanish_stem(w: str) -> str:
    return _strip_suffixes(w, _ES_SUFFIXES) if len(w) > 4 else w


def italian_stem(w: str) -> str:
    return _strip_suffixes(w, _IT_SUFFIXES) if len(w) > 4 else w


def portuguese_stem(w: str) -> str:
    return _strip_suffixes(w, _PT_SUFFIXES) if len(w) > 4 else w


def dutch_stem(w: str) -> str:
    return _strip_suffixes(w, _NL_SUFFIXES, min_stem=4) if len(w) > 4 else w


def russian_stem(w: str) -> str:
    if len(w) <= 4:
        return w
    for r in _RU_REFLEXIVE:
        if w.endswith(r) and len(w) - len(r) >= 3:
            w = w[: len(w) - len(r)]
            break
    return _strip_suffixes(w, _RU_SUFFIXES)


#: Scandinavian: sv/no/da share the -en/-et/-er/-ene noun machinery
_SV_SUFFIXES = [
    ("heterna", "het"), ("heten", "het"), ("heter", "het"),
    ("arna", ""), ("erna", ""), ("orna", ""), ("ande", ""), ("ende", ""),
    ("aste", ""), ("arne", ""), ("aren", ""), ("ades", ""), ("are", ""),
    ("ade", ""), ("at", ""), ("ad", ""), ("en", ""), ("ar", ""),
    ("er", ""), ("or", ""), ("et", ""), ("a", ""), ("e", ""), ("s", ""),
]
_NO_DA_SUFFIXES = [
    ("hetene", "het"), ("heten", "het"), ("heter", "het"),
    ("erne", ""), ("ende", ""), ("ene", ""), ("ane", ""), ("else", ""),
    ("ere", ""), ("est", ""), ("et", ""), ("en", ""), ("er", ""),
    ("ar", ""), ("te", ""), ("e", ""), ("s", ""),
]
#: Finnish: strip possessives then the most common case endings (a real
#: Snowball Finnish is far deeper; goal is stable collisions)
_FI_SUFFIXES = [
    ("issaan", ""), ("issään", ""), ("llaan", ""), ("llään", ""),
    ("ssaan", ""), ("ssään", ""), ("iensa", ""), ("iensä", ""),
    ("isiin", ""), ("ista", ""), ("istä", ""), ("ille", ""),
    ("illa", ""), ("illä", ""), ("issa", ""), ("issä", ""),
    ("lla", ""), ("llä", ""), ("ssa", ""), ("ssä", ""), ("sta", ""),
    ("stä", ""), ("lle", ""), ("lta", ""), ("ltä", ""), ("ksi", ""),
    ("tta", ""), ("ttä", ""), ("ien", ""), ("in", ""), ("it", ""),
    ("et", ""), ("at", ""), ("ät", ""), ("na", ""), ("nä", ""),
    ("a", ""), ("ä", ""), ("n", ""), ("t", ""),
]
#: Hungarian: case endings + plural
_HU_SUFFIXES = [
    ("jainak", ""), ("einek", ""), ("oknak", ""), ("eknek", ""),
    ("ságok", "ság"), ("ségek", "ség"), ("ság", "ság"), ("ség", "ség"),
    ("okat", ""), ("eket", ""), ("akat", ""), ("ban", ""), ("ben", ""),
    ("nak", ""), ("nek", ""), ("val", ""), ("vel", ""), ("ból", ""),
    ("ből", ""), ("hoz", ""), ("hez", ""), ("ról", ""), ("ről", ""),
    ("ok", ""), ("ek", ""), ("ak", ""), ("ot", ""), ("et", ""),
    ("at", ""), ("on", ""), ("en", ""), ("án", ""), ("én", ""),
    ("t", ""), ("k", ""),
]
#: Turkish: agglutinative chain simplified to the outermost layers
_TR_SUFFIXES = [
    ("larından", ""), ("lerinden", ""), ("larında", ""), ("lerinde", ""),
    ("larini", ""), ("lerini", ""), ("larına", ""), ("lerine", ""),
    ("ların", ""), ("lerin", ""), ("ları", ""), ("leri", ""),
    ("lardan", ""), ("lerden", ""), ("larda", ""), ("lerde", ""),
    ("lara", ""), ("lere", ""), ("lar", ""), ("ler", ""),
    ("ında", ""), ("inde", ""), ("undan", ""), ("ünden", ""),
    ("dan", ""), ("den", ""), ("tan", ""), ("ten", ""),
    ("da", ""), ("de", ""), ("ta", ""), ("te", ""),
    ("ın", ""), ("in", ""), ("un", ""), ("ün", ""),
    ("ı", ""), ("i", ""), ("u", ""), ("ü", ""), ("a", ""), ("e", ""),
]
#: Polish: declension + common verb endings
_PL_SUFFIXES = [
    ("owaniach", ""), ("owania", ""), ("owanie", ""), ("ościach", "ość"),
    ("ościami", "ość"), ("ości", "ość"), ("ość", "ość"),
    ("owych", "owy"), ("owymi", "owy"), ("owej", "owy"), ("owego", "owy"),
    ("owy", "owy"), ("owa", "owy"), ("owe", "owy"),
    ("ach", ""), ("ami", ""), ("iem", ""), ("em", ""), ("om", ""),
    ("ów", ""), ("ej", ""), ("ego", ""), ("emu", ""), ("ymi", ""),
    ("ych", ""), ("ą", ""), ("ę", ""), ("y", ""), ("i", ""), ("e", ""),
    ("a", ""), ("o", ""), ("u", ""),
]
#: Romanian: articles + plural/case
_RO_SUFFIXES = [
    ("iilor", ""), ("ilor", ""), ("ului", ""), ("elor", ""),
    ("ările", "are"), ("area", "are"), ("erea", "ere"), ("irea", "ire"),
    ("ări", "are"), ("uri", ""), ("ele", ""), ("ea", ""), ("ul", ""),
    ("ii", ""), ("le", ""), ("lui", ""), ("ă", ""), ("a", ""),
    ("e", ""), ("i", ""), ("u", ""),
]
#: Czech: declension
_CS_SUFFIXES = [
    ("ováním", "ování"), ("ování", "ování"), ("ostech", "ost"),
    ("ostem", "ost"), ("ostí", "ost"), ("osti", "ost"), ("ost", "ost"),
    ("ého", ""), ("ému", ""), ("ými", ""), ("ých", ""), ("ami", ""),
    ("emi", ""), ("ech", ""), ("ích", ""), ("ům", ""), ("em", ""),
    ("ou", ""), ("y", ""), ("i", ""), ("e", ""), ("é", ""),
    ("á", ""), ("í", ""), ("ý", ""), ("a", ""), ("o", ""), ("u", ""),
]


def swedish_stem(w: str) -> str:
    return _strip_suffixes(w, _SV_SUFFIXES) if len(w) > 4 else w


def norwegian_stem(w: str) -> str:
    return _strip_suffixes(w, _NO_DA_SUFFIXES) if len(w) > 4 else w


def danish_stem(w: str) -> str:
    return _strip_suffixes(w, _NO_DA_SUFFIXES) if len(w) > 4 else w


def finnish_stem(w: str) -> str:
    return _strip_suffixes(w, _FI_SUFFIXES) if len(w) > 5 else w


def hungarian_stem(w: str) -> str:
    return _strip_suffixes(w, _HU_SUFFIXES) if len(w) > 4 else w


def turkish_stem(w: str) -> str:
    if len(w) <= 4:
        return w
    # peel at most two agglutinated layers
    w1 = _strip_suffixes(w, _TR_SUFFIXES)
    return _strip_suffixes(w1, _TR_SUFFIXES) if len(w1) > 5 else w1


def polish_stem(w: str) -> str:
    return _strip_suffixes(w, _PL_SUFFIXES) if len(w) > 3 else w


def romanian_stem(w: str) -> str:
    return _strip_suffixes(w, _RO_SUFFIXES) if len(w) > 4 else w


def czech_stem(w: str) -> str:
    return _strip_suffixes(w, _CS_SUFFIXES) if len(w) > 4 else w


#: language → stemmer for TextTokenizer(stemming=True, language=...)
#: (reference: Lucene ships ~30 per-language Snowball analyzers,
#: LuceneTextAnalyzer.scala:203 — 17 light analogs here)
STEMMERS = {"en": porter_stem, "fr": french_stem, "de": german_stem,
            "es": spanish_stem, "it": italian_stem, "pt": portuguese_stem,
            "nl": dutch_stem, "ru": russian_stem,
            "sv": swedish_stem, "no": norwegian_stem, "da": danish_stem,
            "fi": finnish_stem, "hu": hungarian_stem, "tr": turkish_stem,
            "pl": polish_stem, "ro": romanian_stem, "cs": czech_stem}


class TextTokenizer(UnaryTransformer):
    """Text → TextList (reference TextTokenizer.scala:196). ``stemming``
    applies the ``language``'s stemmer to every token (reference Lucene
    analyzers stem per-language, LuceneTextAnalyzer.scala:203; en/fr/de/es
    here — other languages pass through untouched)."""

    def __init__(self, min_token_length: int = TransmogrifierDefaults.MinTokenLength,
                 stemming: bool = False, language: str = "en", uid=None):
        stem = STEMMERS.get(language, lambda t: t)

        def fn(v):
            toks = tokenize_text(v, min_token_length)
            return [stem(t) for t in toks] if stemming else toks
        super().__init__(
            "tokenize", transform_fn=fn,
            output_type=TextList, input_type=Text, uid=uid)
        self.min_token_length = min_token_length
        self.stemming = stemming
        self.language = language


def _hash_token(tok: str, num_hashes: int) -> int:
    """Stable token → bin (crc32; the reference uses MurmurHash3 via Spark's
    HashingTF — any stable uniform hash serves)."""
    return zlib.crc32(tok.encode("utf-8")) % num_hashes


def tokenize_hash_texts(docs: Sequence[Optional[str]], num_hashes: int,
                        min_token_length: int = 1,
                        binary: bool = False) -> np.ndarray:
    """Fused tokenize + hashing-trick counts for a document batch: the
    native C kernel handles ASCII docs (native/text_ops.cpp), the
    Unicode-aware Python tokenizer fills in the flagged rows — results are
    identical to tokenize_text + hash_token_lists by construction."""
    return _tokenize_hash_counted(docs, num_hashes, min_token_length,
                                  binary)[0]


def _tokenize_hash_counted(docs: Sequence[Optional[str]], num_hashes: int,
                           min_token_length: int = 1, binary: bool = False
                           ) -> Tuple[np.ndarray, str, int, int]:
    """``tokenize_hash_texts``'s block with what its span says of it: which
    ``path`` the documents took (``native`` where the C kernel ran,
    ``python`` where there is none), the rows the Python tokenizer took and
    the tokens counted, each counted where it is met."""
    from ...utils.text_native import tokenize_hash_native
    res = tokenize_hash_native(docs, num_hashes, min_token_length, binary)
    if res is None:
        lists = [tokenize_text(d, min_token_length) for d in docs]
        return (hash_token_lists(lists, num_hashes, binary), "python",
                len(docs), sum(map(len, lists)))
    counts, needs_py, tokens = res
    idx = np.nonzero(needs_py)[0]
    if len(idx):
        lists = [tokenize_text(docs[i], min_token_length) for i in idx]
        counts[idx] = hash_token_lists(lists, num_hashes, binary)
        tokens += sum(map(len, lists))
    return counts, "native", len(idx), tokens


def hash_token_lists(token_lists: Sequence[Sequence[str]], num_hashes: int,
                     binary: bool = False) -> np.ndarray:
    from ...utils.text_native import hash_token_lists_native
    native = hash_token_lists_native(token_lists, num_hashes, binary)
    if native is not None:
        return native
    out = np.zeros((len(token_lists), num_hashes), dtype=np.float32)
    for i, toks in enumerate(token_lists):
        for t in toks or ():
            out[i, _hash_token(t, num_hashes)] += 1.0
    if binary:
        np.minimum(out, 1.0, out=out)
    return out


class HashingVectorizer(SequenceTransformer):
    """Seq[TextList] → OPVector via the hashing trick (reference
    OPCollectionHashingVectorizer.scala:398 — shared or separate hash space)."""

    output_type = OPVector

    def __init__(self, num_hashes: int = TransmogrifierDefaults.NumHashes,
                 shared_hash_space: bool = False, binary_freq: bool = False,
                 uid=None):
        super().__init__("vecHash", transform_fn=None, output_type=OPVector, uid=uid)
        self.num_hashes = num_hashes
        self.shared_hash_space = shared_hash_space
        self.binary_freq = binary_freq

    def transform_column(self, table: FeatureTable) -> Column:
        blocks, meta = [], []
        if self.shared_hash_space:
            n = table.num_rows
            block = np.zeros((n, self.num_hashes), dtype=np.float32)
            for f in self.input_features:
                vals = np.asarray(table[f.name].values)
                block += hash_token_lists(vals, self.num_hashes, self.binary_freq)
            blocks.append(block)
            meta.extend([VectorColumnMetadata(
                "+".join(fe.name for fe in self.input_features), "TextList",
                None, None, descriptor_value=f"hash_{j}")
                for j in range(self.num_hashes)])
        else:
            for f in self.input_features:
                vals = np.asarray(table[f.name].values)
                blocks.append(hash_token_lists(vals, self.num_hashes, self.binary_freq))
                meta.extend([VectorColumnMetadata(
                    f.name, f.type_name, f.name, None,
                    descriptor_value=f"hash_{j}") for j in range(self.num_hashes)])
        vm = VectorMetadata.of(self.get_output().name, meta)
        return Column(OPVector, np.concatenate(blocks, axis=1), None,
                      {"vector_meta": vm})

    def transform_row(self, row: Dict[str, Any]) -> Any:
        one = FeatureTable(
            {f.name: Column.of_values(f.feature_type, [row.get(f.name)])
             for f in self.input_features}, 1)
        return np.asarray(self.transform_column(one).values)[0].tolist()


class SmartTextVectorizer(Estimator):
    """Seq[Text] → OPVector: per-feature cardinality decides pivot vs hashing
    (reference SmartTextVectorizer.scala:260 — cardinality stats then ≤maxCard
    → one-hot pivot else hashing trick; tracks nulls either way)."""

    output_type = OPVector

    def __init__(self, max_cardinality: int = TransmogrifierDefaults.MaxCardinality,
                 top_k: int = TransmogrifierDefaults.TopK,
                 min_support: int = TransmogrifierDefaults.MinSupport,
                 num_hashes: int = TransmogrifierDefaults.NumHashes,
                 track_nulls: bool = TransmogrifierDefaults.TrackNulls,
                 uid=None):
        super().__init__("smartTxtVec", uid)
        self.max_cardinality = max_cardinality
        self.top_k = top_k
        self.min_support = min_support
        self.num_hashes = num_hashes
        self.track_nulls = track_nulls

    def fit(self, table: FeatureTable) -> Transformer:
        plans: List[Dict[str, Any]] = []
        for f in self.input_features:
            with _obs_span("text.cardinality", cat="train", column=f.name,
                           rows=table.num_rows) as card_span:
                col = table[f.name]
                vals = np.asarray(col.values)
                m = col.valid_mask()
                _, cnt, _, _ = _factorize_valid(vals, m)
                if len(cnt) <= self.max_cardinality:
                    plans.append({"kind": "pivot", "vocab": _top_levels(
                        cnt, self.min_support, self.top_k)})
                else:
                    plans.append({"kind": "hash"})
                card_span.set_attr(distinct=len(cnt),
                                   plan=plans[-1]["kind"])
        model = SmartTextVectorizerModel(
            plans=plans, num_hashes=self.num_hashes, track_nulls=self.track_nulls)
        return self._finalize_model(model)


class SmartTextVectorizerModel(_VectorModelBase):
    def __init__(self, plans: List[Dict[str, Any]], num_hashes: int,
                 track_nulls: bool, uid=None):
        super().__init__("smartTxtVec", uid)
        self.plans = plans
        self.num_hashes = num_hashes
        self.track_nulls = track_nulls

    def transform_column(self, table: FeatureTable) -> Column:
        n = table.num_rows
        blocks, meta = [], []
        for f, plan in zip(self.input_features, self.plans):
            col = table[f.name]
            vals = np.asarray(col.values)
            m = col.valid_mask()
            if plan["kind"] == "pivot":
                vocab = plan["vocab"]
                k = len(vocab)
                with _obs_span("text.pivot", column=f.name, rows=n,
                               levels=k):
                    block = np.zeros((n, k + 1), dtype=np.float32)
                    index = {v: i for i, v in enumerate(vocab)}
                    block[m, _encode_valid(vals, m, index, False)[0][m]] = 1.0
                blocks.append(block)
                meta.extend(_meta_cols(
                    f, [(f.name, v) for v in vocab] + [(f.name, OTHER_INDICATOR)]))
            else:
                with _obs_span("text.hash", column=f.name, rows=n,
                               bins=self.num_hashes) as hash_span:
                    block, path, py_rows, tokens = _tokenize_hash_counted(
                        [v if ok else None for v, ok in zip(vals, m)],
                        self.num_hashes)
                    hash_span.set_attr(path=path, pyRows=py_rows,
                                       tokens=tokens,
                                       bytes=int(block.nbytes))
                blocks.append(block)
                meta.extend([VectorColumnMetadata(
                    f.name, f.type_name, f.name, None,
                    descriptor_value=f"hash_{j}") for j in range(self.num_hashes)])
            if self.track_nulls:
                blocks.append((~m).astype(np.float32)[:, None])
                meta.extend(_meta_cols(f, [(f.name, NULL_INDICATOR)]))
        with _obs_span("text.concat", columns=len(blocks)) as concat_span:
            out = self._emit(np.concatenate(blocks, axis=1), meta)
            concat_span.set_attr(bytes=int(out.values.nbytes))
        return out


# ---------------------------------------------------------------------------
# Combiner
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("widths", "mesh"))
def _join_columns(parts, widths: Tuple[int, ...], mesh):
    """Device arrays of the same rows, each ``(n, w)`` or ``(n,)``, → the
    ``(n, sum(widths))`` float32 matrix of them side by side, its rows
    sharded over ``mesh``'s 'data' axis where there is one."""
    out = jnp.concatenate([p.reshape(-1, w).astype(jnp.float32)
                           for p, w in zip(parts, widths)], axis=1)
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(out, row_sharding(mesh, 2))
    return out


def _say_on_stage_span(**attrs: Any) -> None:
    """Attributes on the ``stage.transform`` span around the caller, where
    there is one."""
    s = _obs_tracer().current()
    if s is not None and s.name == "stage.transform":
        s.set_attr(**attrs)


class VectorsCombiner(SequenceTransformer):
    """Seq[OPVector] → OPVector concatenation with metadata flattening
    (reference VectorsCombiner.scala:89)."""

    output_type = OPVector

    def __init__(self, uid=None):
        super().__init__("combined", transform_fn=None, output_type=OPVector, uid=uid)
        self.mesh = None

    def set_mesh(self, mesh) -> "VectorsCombiner":
        """Upload the combined matrix row-sharded over the mesh's 'data'
        axis, so every downstream consumer reads an already-distributed
        buffer (SURVEY §2.10 P1)."""
        self.mesh = mesh
        return self

    def device_columnar(self, env):
        """Pure-jax dual for the fused serve program (see RealVectorizerModel)."""
        import jax.numpy as jnp
        blocks = []
        for f in self.input_features:
            vals, _ = env[f.name]
            blocks.append(vals[:, None] if vals.ndim == 1
                          else vals.astype(jnp.float32))
        return jnp.concatenate(blocks, axis=1), None

    def transform_column(self, table: FeatureTable) -> Column:
        """One device matrix of the inputs side by side, which every
        downstream consumer (SanityChecker, ModelSelector, scoring) reuses.
        From ``_DEVICE_BLOCK_MIN_ROWS`` rows on, an input that is on the
        device stays there, each host input goes up by itself and one
        program joins them; a smaller table is joined on the host and goes
        up whole. Under the mesh what goes up goes as shards of rows."""
        n = table.num_rows
        mesh = _rows_mesh(getattr(self, "mesh", None), n)
        arrs, widths, metas = [], [], []
        for f in self.input_features:
            col = table[f.name]
            arrs.append(col.values)
            widths.append(col.width)
            vm = col.metadata.get("vector_meta")
            if vm is None:
                vm = VectorMetadata.of(f.name, [
                    VectorColumnMetadata(f.name, f.type_name, None, None,
                                         descriptor_value=f"col_{j}")
                    for j in range(widths[-1])])
            metas.append(vm)
        vm = VectorMetadata.flatten(self.get_output().name, metas)
        on_device = [isinstance(a, jax.Array) for a in arrs]
        if (len(arrs) == 1 and on_device[0] and arrs[0].ndim == 2
                and arrs[0].dtype == jnp.float32):
            mat, stayed, h2d_bytes = arrs[0], 1, 0    # handed on as it is
        elif n < _DEVICE_BLOCK_MIN_ROWS or len(arrs) == 1:
            blocks = [np.asarray(a, dtype=np.float32).reshape(n, w)
                      for a, w in zip(arrs, widths)]
            mat = blocks[0] if len(blocks) == 1 else np.concatenate(
                blocks, axis=1)
            stayed, h2d_bytes = 0, mat.nbytes
            mat = _upload(mat, mesh, "combiner.upload")
        else:
            parts, stayed, h2d_bytes = [], sum(on_device), 0
            for a, dev in zip(arrs, on_device):
                if not dev:
                    a = np.asarray(a, dtype=np.float32)
                    h2d_bytes += a.nbytes
                    a = _upload(a, mesh, "combiner.upload")
                elif mesh is not None and not a.sharding.is_equivalent_to(
                        row_sharding(mesh, a.ndim), a.ndim):
                    a = jax.device_put(a, row_sharding(mesh, a.ndim))
                parts.append(a)
            mat = _join_columns(tuple(parts), widths=tuple(widths),
                                mesh=mesh)
        assert vm.size == mat.shape[1], (vm.size, mat.shape)
        _say_on_stage_span(deviceInputs=stayed, hostInputs=len(arrs) - stayed,
                           h2dBytes=h2d_bytes)
        return Column(OPVector, mat, None, {"vector_meta": vm})

    def transform_row(self, row: Dict[str, Any]) -> Any:
        out: List[float] = []
        for f in self.input_features:
            v = row.get(f.name) or []
            out.extend(float(x) for x in (v if isinstance(v, (list, tuple)) else [v]))
        return out
