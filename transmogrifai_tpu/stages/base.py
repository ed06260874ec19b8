"""Pipeline stage base classes.

Mirrors the reference stage hierarchy (reference:
features/src/main/scala/com/salesforce/op/stages/OpPipelineStages.scala:56-553,
base/unary/UnaryEstimator.scala, base/binary, base/ternary, base/quaternary,
base/sequence, FeatureGeneratorStage.scala:62-110) with a columnar twist:

* the primary execution path is **columnar** — ``Transformer.transform(table)``
  returns a whole output ``Column``, typically produced by a jitted kernel over
  device arrays (the analog of the reference fusing all row lambdas of a DAG
  layer into one RDD map, FitStagesUtil.scala:96-119; here XLA does the fusing);
* every transformer also exposes the row-level dual ``transform_row(row)`` — the
  equivalent of the reference's ``OpTransformer.transformKeyValue`` contract
  (OpPipelineStages.scala:527-553) that powers Spark-free local scoring.
"""
from __future__ import annotations

import abc
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Type

import numpy as np

from ..features import Feature, make_uid
from ..table import Column, FeatureTable
from ..types import FeatureType, OPVector


#: class-name → stage class, the analog of the reference's reflection-based
#: stage reader (OpPipelineStageReader.scala) resolving classes by name
STAGE_REGISTRY: Dict[str, type] = {}


class OpPipelineStage(abc.ABC):
    """Base of every stage: typed inputs, single typed output, params
    (reference OpPipelineStageBase, OpPipelineStages.scala:56-162)."""

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        STAGE_REGISTRY[cls.__name__] = cls

    #: input feature types; None entries mean "any feature type"
    input_types: Tuple[Optional[Type[FeatureType]], ...] = ()
    #: output feature type
    output_type: Type[FeatureType] = OPVector

    def __init__(self, operation_name: str, uid: Optional[str] = None):
        self.operation_name = operation_name
        self.uid = uid or make_uid(type(self).__name__)
        self.input_features: Tuple[Feature, ...] = ()
        self._output_feature: Optional[Feature] = None
        self._params: Dict[str, Any] = {}

    # -- wiring --------------------------------------------------------------
    def set_input(self, *features: Feature) -> "OpPipelineStage":
        self._check_input_length(features)
        for i, (f, expected) in enumerate(zip(features, self._expected_types(features))):
            if expected is not None and not issubclass(f.feature_type, expected):
                raise TypeError(
                    f"{type(self).__name__} input {i} must be {expected.__name__}, "
                    f"got {f.type_name} (feature '{f.name}')")
        self.input_features = tuple(features)
        self._output_feature = None
        return self

    def _check_input_length(self, features: Sequence[Feature]) -> None:
        if self.input_types and len(features) != len(self.input_types):
            raise ValueError(
                f"{type(self).__name__} takes {len(self.input_types)} inputs, "
                f"got {len(features)}")

    def _expected_types(self, features: Sequence[Feature]):
        if self.input_types:
            return self.input_types
        return (None,) * len(features)

    @property
    def input_names(self) -> Tuple[str, ...]:
        return tuple(f.name for f in self.input_features)

    def output_name(self) -> str:
        base = "-".join(self.input_names) if self.input_features else self.operation_name
        if len(base) > 64:
            # deep DAGs would otherwise double name length per level
            import hashlib
            base = base[:48] + "-" + hashlib.md5(base.encode()).hexdigest()[:8]
        return f"{base}_{self.operation_name}_{self.uid.rsplit('_', 1)[-1]}"

    def output_is_response(self) -> bool:
        """Output is a response iff any input is (reference
        OpPipelineStage.outputIsResponse); stages mixing in AllowLabelAsInput
        override to False."""
        return any(f.is_response for f in self.input_features)

    def get_output(self) -> Feature:
        if self._output_feature is None:
            self._output_feature = Feature(
                name=self.output_name(), feature_type=self.output_type,
                is_response=self.output_is_response(), origin_stage=self,
                parents=self.input_features)
        return self._output_feature

    # -- params (analog of Spark ML Params + OpParams injection) -------------
    def set_params(self, **kv) -> "OpPipelineStage":
        for k, v in kv.items():
            if not hasattr(self, k):
                raise ValueError(f"{type(self).__name__} has no param '{k}'")
            setattr(self, k, v)
        return self

    def get_params(self) -> Dict[str, Any]:
        return {k: v for k, v in vars(self).items()
                if not k.startswith("_") and k not in (
                    "input_features", "operation_name", "uid")}

    def __repr__(self) -> str:
        return f"{type(self).__name__}(uid={self.uid!r})"


class AllowLabelAsInput:
    """Lets a stage consume the label without marking its output as response
    (reference OpPipelineStages.scala:204-211; used by SanityChecker, LOCO)."""

    def output_is_response(self) -> bool:
        return False


class Transformer(OpPipelineStage):
    """A fitted/stateless stage that maps a table to one new column."""

    @abc.abstractmethod
    def transform_column(self, table: FeatureTable) -> Column:
        """Columnar path: compute the whole output column (device kernels)."""

    def transform(self, table: FeatureTable) -> FeatureTable:
        out = self.get_output()
        return table.with_column(out.name, self.transform_column(table))

    # row-level dual (reference OpTransformer.transformKeyValue)
    def transform_row(self, row: Dict[str, Any]) -> Any:
        """Single-row scoring path. Default: delegate to transform_fn if the
        subclass defines one, else run the columnar path on a 1-row table."""
        fn = getattr(self, "transform_fn", None)
        if fn is not None:
            args = [row.get(f.name) for f in self.input_features]
            return fn(*args)
        one = FeatureTable(
            {f.name: Column.of_values(f.feature_type, [row.get(f.name)])
             for f in self.input_features}, 1)
        out = self.transform_column(one)
        if out.mask is not None and not bool(np.asarray(out.mask)[0]):
            return None
        v = np.asarray(out.values)[0]
        return v.tolist() if isinstance(v, np.ndarray) else (
            v.item() if isinstance(v, np.generic) else v)


class PendingFit:
    """A dispatched-but-unsynced estimator fit: the device stat programs are
    queued, the host decision logic waits in ``finish``. Lets a caller
    (workflow-level CV pass 1, model_selector.py) queue F folds' fits
    back-to-back and pay ONE host transfer instead of F serial round-trips
    (the reference's analog: concurrent fold Futures,
    OpValidator.applyDAG :228-256)."""

    def __init__(self, dev: Dict[str, Any], finish: Callable[[Dict[str, Any]],
                                                             "Transformer"]):
        self.dev = dev          # name -> device array, still materializing
        self._finish = finish   # host dict (same keys, np arrays) -> model


def materialize_pending(pendings: "List[PendingFit]") -> "List[Transformer]":
    """Resolve many queued fits: one fused fetch (:func:`fetch_pending`),
    then each fit's host decisions."""
    return [p._finish(h) for p, h in zip(pendings, fetch_pending(pendings))]


def fetch_pending(pendings: "List[PendingFit]") -> "List[Dict[str, Any]]":
    """The queued fits' leaves on the host, one dict a fit, with ONE host
    transfer per dtype: all pending device leaves concatenate into flat
    vectors (grouped by dtype —
    casting counts through f32 would round above 2^24), transfer once, and
    split back. Every np.asarray on a device leaf is a blocking sync with a
    fixed cost whatever its size, so F·|leaves| separate calls would cost
    more than the stat kernels they read."""
    import jax.numpy as jnp
    leaves = []               # (pending_idx, key, shape, dtype)
    by_dtype: Dict[Any, list] = {}
    for pi, p in enumerate(pendings):
        for k, v in p.dev.items():
            if isinstance(v, np.ndarray):
                # host leaves keep their exact dtype (jnp.asarray would
                # silently narrow f64/i64 under the default x64-off
                # config — the rounding hazard this function's per-dtype
                # grouping exists to avoid)
                leaves.append((pi, k, None, None))
                continue
            v = jnp.asarray(v)
            leaves.append((pi, k, v.shape, v.dtype))
            by_dtype.setdefault(str(v.dtype), []).append(v.reshape(-1))
    flat_host = {dt: np.asarray(jnp.concatenate(vs)) if len(vs) > 1
                 else np.asarray(vs[0])
                 for dt, vs in by_dtype.items()}
    offs = {dt: 0 for dt in flat_host}
    host_dicts: List[Dict[str, Any]] = [{} for _ in pendings]
    for pi, k, shape, dtype in leaves:
        if shape is None:          # host leaf, passed through untouched
            host_dicts[pi][k] = pendings[pi].dev[k]
            continue
        dt = str(dtype)
        size = int(np.prod(shape)) if shape else 1
        host_dicts[pi][k] = flat_host[dt][offs[dt]:offs[dt] + size
                                          ].reshape(shape)
        offs[dt] += size
    return host_dicts


class Estimator(OpPipelineStage):
    """A stage that must be fit on data, producing a Transformer model
    (reference Unary/Binary/…Estimator fitFn pattern)."""

    @abc.abstractmethod
    def fit(self, table: FeatureTable) -> Transformer:
        """Fit on the table and return the fitted model transformer. The model
        MUST reuse this stage's uid and output feature so DAG wiring holds
        (reference: model uid == estimator uid)."""

    def fit_queued(self, table: FeatureTable) -> PendingFit:
        """Queued-fit protocol: dispatch the device stat programs and defer
        the host sync + decision logic to ``PendingFit.finish``. The default
        wraps plain ``fit`` (sync happens inside it); estimators whose fit
        is transfer-latency-bound override this (SanityChecker)."""
        model = self.fit(table)
        return PendingFit({}, lambda _h: model)

    def _finalize_model(self, model: Transformer) -> Transformer:
        model.uid = self.uid
        model.input_features = self.input_features
        # keep the estimator's naming so output feature names stay stable
        model.operation_name = self.operation_name
        model.output_type = self.output_type
        model._output_feature = self.get_output()
        return model


class FeatureGeneratorStage(OpPipelineStage):
    """Origin stage of raw features: holds the record-level ``extract_fn`` and
    the optional event-aggregation monoid (reference
    FeatureGeneratorStage.scala:62-110)."""

    def __init__(self, extract_fn: Callable[[Any], Any], output_name: str,
                 output_type: Type[FeatureType], is_response: bool,
                 aggregator: Optional[Any] = None,
                 aggregate_window: Optional[int] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name=f"generate_{output_name}", uid=uid)
        self.extract_fn = extract_fn
        self.output_type = output_type
        self.is_response = is_response
        self.aggregator = aggregator
        self.aggregate_window = aggregate_window
        self._raw_name = output_name

    def output_name(self) -> str:
        return self._raw_name

    def output_is_response(self) -> bool:
        return self.is_response

    def extract(self, record: Any) -> Any:
        v = self.extract_fn(record)
        if isinstance(v, FeatureType):
            return v.value
        return v


# ---------------------------------------------------------------------------
# Arity-typed lambda stages (reference base/unary/.., base/sequence/..)
# ---------------------------------------------------------------------------

def _iter_cell_values(cols: Sequence[Column]):
    """Iterate rows over just these columns, yielding python values (None =
    missing) — avoids materializing whole-table rows in lambda fallbacks."""
    n = len(cols[0]) if cols else 0
    arrs = [np.asarray(c.values) for c in cols]
    masks = [c.valid_mask() for c in cols]
    for i in range(n):
        out = []
        for a, m in zip(arrs, masks):
            if not m[i]:
                out.append(None)
            else:
                v = a[i]
                out.append(v.tolist() if isinstance(v, np.ndarray) else (
                    v.item() if isinstance(v, np.generic) else v))
        yield tuple(out)


def _vectorized_value_transform(transform_fn: Callable[..., Any],
                                output_type: Type[FeatureType],
                                cols: Sequence[Column]) -> Optional[Column]:
    """Whole-column numpy fast path for value-level lambdas: when every
    input column is numeric and fully valid (no ``None`` the lambda could
    see), apply ``transform_fn`` to the arrays directly — arithmetic
    lambdas are ufunc-compatible and run in one vectorized sweep instead of
    a python loop rebuilding a list per cell. Returns None (→ row-map
    fallback) when inputs are object/masked, the fn rejects arrays
    (truthiness / branching lambdas raise), or the result doesn't look like
    one value per row. The produced Column replicates ``of_values``
    semantics exactly: NaN results are missing (mask False, slot 0)."""
    kind = output_type.column_kind
    if kind not in ("real", "binary", "integral") or not cols:
        return None
    n = len(cols[0])
    if n == 0:     # zero-row probes: the row map is free and warning-free
        return None
    arrs = []
    for c in cols:
        a = np.asarray(c.values)
        if a.dtype.kind not in "fiub" or a.ndim != 1:
            return None
        if c.mask is not None and not np.asarray(c.mask).all():
            return None
        # mirror the row map's value types exactly: ``.item()`` hands the
        # lambda python floats (f64) / ints, so compute in f64/int64 — a
        # float32 sweep would round transcendentals differently
        arrs.append(a.astype(np.float64) if a.dtype.kind in "fb"
                    else a.astype(np.int64))
    try:
        out = transform_fn(*arrs)
    except Exception:
        return None
    if not isinstance(out, np.ndarray) or out.shape != (n,) \
            or out.dtype.kind not in "fiub":
        return None
    missing = np.isnan(out) if out.dtype.kind == "f" else np.zeros(n, bool)
    mask = ~missing
    if kind == "real":
        vals = np.where(missing, 0.0, out).astype(np.float32)
    elif kind == "binary":
        vals = np.where(missing, False,
                        out != 0).astype(np.float32)
    else:  # integral → host int64 (reference Long semantics)
        vals = np.where(missing, 0, out).astype(np.int64)
    return Column(output_type, vals, mask)


class _LambdaTransformer(Transformer):
    """Shared machinery: a value-level ``transform_fn`` over plain python values
    (None == missing) plus an optional ``columnar_fn`` over Columns. Without a
    columnar_fn the transform tries a vectorized numpy sweep
    (:func:`_vectorized_value_transform`) and only then falls back to a
    host-side row map — which remains exactly where it belongs: string-ish
    object columns and lambdas that branch per value."""

    def __init__(self, operation_name: str,
                 transform_fn: Callable[..., Any],
                 output_type: Type[FeatureType],
                 columnar_fn: Optional[Callable[..., Column]] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name, uid)
        self.transform_fn = transform_fn
        self.output_type = output_type
        self.columnar_fn = columnar_fn

    def transform_column(self, table: FeatureTable) -> Column:
        cols = [table[f.name] for f in self.input_features]
        if self.columnar_fn is not None:
            return self.columnar_fn(*cols)
        out = _vectorized_value_transform(self.transform_fn,
                                          self.output_type, cols)
        if out is not None:
            return out
        vals = [self.transform_fn(*args) for args in _iter_cell_values(cols)]
        return Column.of_values(self.output_type, vals)


class UnaryTransformer(_LambdaTransformer):
    """fn: I → O (reference base/unary/UnaryTransformer.scala)."""

    def __init__(self, operation_name, transform_fn, output_type,
                 input_type: Optional[Type[FeatureType]] = None, **kw):
        super().__init__(operation_name, transform_fn, output_type, **kw)
        self.input_types = (input_type,)


class BinaryTransformer(_LambdaTransformer):
    """fn: (I1, I2) → O (reference base/binary/BinaryTransformer.scala)."""

    def __init__(self, operation_name, transform_fn, output_type,
                 input_types: Tuple = (None, None), **kw):
        super().__init__(operation_name, transform_fn, output_type, **kw)
        self.input_types = tuple(input_types)


class TernaryTransformer(_LambdaTransformer):
    def __init__(self, operation_name, transform_fn, output_type,
                 input_types: Tuple = (None, None, None), **kw):
        super().__init__(operation_name, transform_fn, output_type, **kw)
        self.input_types = tuple(input_types)


class QuaternaryTransformer(_LambdaTransformer):
    def __init__(self, operation_name, transform_fn, output_type,
                 input_types: Tuple = (None, None, None, None), **kw):
        super().__init__(operation_name, transform_fn, output_type, **kw)
        self.input_types = tuple(input_types)


class SequenceTransformer(_LambdaTransformer):
    """Variadic homogeneous inputs → one output (reference
    base/sequence/SequenceTransformer.scala). transform_fn receives a list of
    values; columnar_fn receives the list of Columns."""

    def __init__(self, operation_name, transform_fn, output_type, **kw):
        super().__init__(operation_name, transform_fn, output_type, **kw)

    def _check_input_length(self, features):
        if len(features) < 1:
            raise ValueError(f"{type(self).__name__} needs at least one input")

    def transform_row(self, row: Dict[str, Any]) -> Any:
        if self.transform_fn is not None:
            vals = [row.get(f.name) for f in self.input_features]
            return self.transform_fn(vals)
        # columnar-only stages (vectorizers): run the columnar path on 1 row
        one = FeatureTable(
            {f.name: Column.of_values(f.feature_type, [row.get(f.name)])
             for f in self.input_features}, 1)
        out = self.transform_column(one)
        if out.mask is not None and not bool(np.asarray(out.mask)[0]):
            return None
        v = np.asarray(out.values)[0]
        return v.tolist() if isinstance(v, np.ndarray) else (
            v.item() if isinstance(v, np.generic) else v)

    def transform_column(self, table: FeatureTable) -> Column:
        cols = [table[f.name] for f in self.input_features]
        if self.columnar_fn is not None:
            return self.columnar_fn(cols)
        vals = [self.transform_fn(list(args)) for args in _iter_cell_values(cols)]
        return Column.of_values(self.output_type, vals)


class BinarySequenceTransformer(SequenceTransformer):
    """One distinguished input + variadic homogeneous rest (reference
    base/sequence/BinarySequenceTransformer.scala): transform_fn receives
    (head_value, [rest_values])."""

    def _check_input_length(self, features):
        if len(features) < 2:
            raise ValueError(
                f"{type(self).__name__} needs a head input plus at least one "
                f"sequence input")


class _BinarySequenceEstimatorMixin:
    """fit_fn receives (head_column, [rest_columns]) (reference
    base/sequence/BinarySequenceEstimator.scala)."""

    def fit(self, table):
        cols = [table[f.name] for f in self.input_features]
        state = self.fit_fn(cols[0], cols[1:])
        model = self.make_model(state)
        return self._finalize_model(model)


class _LambdaEstimator(Estimator):
    """Estimator from a fit function: fit_fn(columns...) → transform lambdas."""

    def __init__(self, operation_name: str,
                 fit_fn: Callable[..., Dict[str, Any]],
                 output_type: Type[FeatureType],
                 make_model: Callable[[Dict[str, Any]], Transformer],
                 uid: Optional[str] = None):
        super().__init__(operation_name, uid)
        self.fit_fn = fit_fn
        self.output_type = output_type
        self.make_model = make_model

    def fit(self, table: FeatureTable) -> Transformer:
        cols = [table[f.name] for f in self.input_features]
        state = self.fit_fn(*cols)
        model = self.make_model(state)
        return self._finalize_model(model)


class UnaryEstimator(_LambdaEstimator):
    def __init__(self, operation_name, fit_fn, output_type, make_model,
                 input_type: Optional[Type[FeatureType]] = None, **kw):
        super().__init__(operation_name, fit_fn, output_type, make_model, **kw)
        self.input_types = (input_type,)


class BinaryEstimator(_LambdaEstimator):
    def __init__(self, operation_name, fit_fn, output_type, make_model,
                 input_types: Tuple = (None, None), **kw):
        super().__init__(operation_name, fit_fn, output_type, make_model, **kw)
        self.input_types = tuple(input_types)


class TernaryEstimator(_LambdaEstimator):
    """(reference base/ternary/TernaryEstimator.scala)."""

    def __init__(self, operation_name, fit_fn, output_type, make_model,
                 input_types: Tuple = (None, None, None), **kw):
        super().__init__(operation_name, fit_fn, output_type, make_model, **kw)
        self.input_types = tuple(input_types)


class QuaternaryEstimator(_LambdaEstimator):
    """(reference base/quaternary/QuaternaryEstimator.scala)."""

    def __init__(self, operation_name, fit_fn, output_type, make_model,
                 input_types: Tuple = (None, None, None, None), **kw):
        super().__init__(operation_name, fit_fn, output_type, make_model, **kw)
        self.input_types = tuple(input_types)


class SequenceEstimator(_LambdaEstimator):
    """Variadic homogeneous-input estimator (reference
    base/sequence/SequenceEstimator.scala:57) — base of all multi-feature
    vectorizers."""

    def _check_input_length(self, features):
        if len(features) < 1:
            raise ValueError(f"{type(self).__name__} needs at least one input")

    def fit(self, table: FeatureTable) -> Transformer:
        cols = [table[f.name] for f in self.input_features]
        state = self.fit_fn(cols)
        model = self.make_model(state)
        return self._finalize_model(model)

class BinarySequenceEstimator(_BinarySequenceEstimatorMixin, SequenceEstimator):
    """(reference base/sequence/BinarySequenceEstimator.scala)."""

    def _check_input_length(self, features):
        if len(features) < 2:
            raise ValueError(
                f"{type(self).__name__} needs a head input plus at least one "
                f"sequence input")

