"""Spark-free ("local") serve-time scoring.

The reference's `local` module folds a ``Map[String,Any]`` through each stage's
``transformKeyValue`` row lambda, converting Spark-wrapped models through MLeap
(reference: local/src/main/scala/com/salesforce/op/local/OpWorkflowModelLocal.scala:93-197).
Here every Transformer already exposes the row-level dual ``transform_row``, so
the scorer is simply a fold over the topologically-ordered fitted stages — no
model-conversion layer is needed. For serving at throughput, use
:func:`micro_batch_score_function`, which runs the columnar (jitted) path on
micro-batches — the TPU replacement for MLeap row scoring.
"""
from __future__ import annotations

import logging
import time

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..observability import metrics as _obs_metrics
from ..observability.trace import span as _obs_span
from ..table import Column, FeatureTable

logger = logging.getLogger(__name__)

#: per-row error key emitted by micro-batch quarantine (the row could not be
#: scored; every result feature is None and this key carries the reason)
SCORE_ERROR_KEY = "__score_error__"


class ScoreSchemaError(ValueError):
    """Serve-time input does not match the fitted schema (missing column,
    unconvertible dtype, wrong vector width). Raised with the offending
    column and the expected-vs-actual detail *before* the data reaches the
    jitted program — an XLA trace/shape error names none of that."""


def score_function(model) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
    """Row-at-a-time scorer (reference OpWorkflowModelLocal.scoreFunction).

    Returns ``fn(raw_row) -> {result feature name: value}`` where ``raw_row``
    maps raw feature names to python values (None = missing).
    """
    stages = model.stages  # farthest-first layers == topological order
    result_names = [f.name for f in model.result_features]
    raw_gens = [(f.name, f.origin_stage) for f in model.raw_features]

    def score(row: Dict[str, Any]) -> Dict[str, Any]:
        # per-request latency: the O(1)-memory streaming histogram keeps
        # p50/p95/p99 live over unbounded request streams
        # (docs/observability.md "Scoring telemetry")
        t0 = (time.perf_counter()
              if _obs_metrics.metrics_enabled() else None)
        # raw features come from each generator's extract_fn, exactly like the
        # batch reader path (DataReader.generateDataFrame row build)
        acc = {name: gen.extract(row) for name, gen in raw_gens}
        for stage in stages:
            out = stage.get_output()
            acc[out.name] = stage.transform_row(acc)
        result = {name: acc[name] for name in result_names}
        if t0 is not None:
            _obs_metrics.observe(
                "tg_score_request_seconds", time.perf_counter() - t0,
                help="per-request scoring latency (row path)")
        return result

    return score


def compiled_score_function(model):
    """Fused serve path: ONE jitted XLA program per device-fusable segment.

    A thin consumer of the shared transform-plan compiler
    (``transmogrifai_tpu/plan.py``) — the TPU-first analog of the
    reference's layer fusion + MLeap serving (reference
    FitStagesUtil.applyOpTransformations:96-119,
    OpWorkflowModelLocal.scala:93-197). The planner partitions the fitted
    stage run into host waves (string pivots, tokenizers — eager) and
    device segments (numeric vectorizers → VectorsCombiner → SanityChecker
    keep-slice → traceable Prediction emission — one jit each, reused
    across micro-batches via row bucket padding). What this wrapper adds is
    the serve-time schema guard: descriptive :class:`ScoreSchemaError`
    *before* any data reaches a jitted program.

    Returns ``score(table: FeatureTable) -> FeatureTable`` with the result
    features plus every column the retained host stages produce; fused
    INTERMEDIATE columns not consumed downstream are not materialized —
    XLA dead-code-eliminates them (unlike ``model.score``'s
    keep-everything default).
    """
    from .. import plan as _plan

    stages = list(model.stages)
    result_names = [f.name for f in model.result_features]

    # the fitted column set: every column the serve pass reads that no
    # stage of the model produces must arrive in the input table — checked
    # up front with a descriptive error instead of a KeyError deep in a
    # host stage or a trace error inside XLA
    produced_all = {s.get_output().name for s in stages}
    required_external: List[str] = []
    for s in stages:
        # response features are train-only: scoring never reads the label
        names = (s.device_inputs() if hasattr(s, "device_inputs")
                 else [f.name for f in s.input_features if not f.is_response])
        for nm in names:
            if nm not in produced_all and nm not in required_external:
                required_external.append(nm)

    # fitted input schema for the fused program: per-column trailing shape
    # (vector width). Seeded from the training table when the model still
    # carries one; otherwise pinned by the first scored batch. Violations
    # raise ScoreSchemaError at the boundary instead of a shape/trace error
    # inside XLA (which would also silently recompile on every new width).
    expected_shapes: Dict[str, Tuple[int, ...]] = {}
    ttbl = getattr(model, "train_table", None)
    if ttbl is not None:
        for nm in required_external:
            if nm in ttbl.column_names:
                expected_shapes[nm] = tuple(np.shape(ttbl[nm].values)[1:])

    def _validated_input(tbl: FeatureTable, nm: str) -> Column:
        if nm not in tbl.column_names:
            raise ScoreSchemaError(
                f"input is missing column '{nm}' required by the fitted "
                f"serve program; table has {sorted(tbl.column_names)}")
        col = tbl[nm]
        try:
            v = np.asarray(col.values, dtype=np.float32)
        except (TypeError, ValueError) as e:
            dt = getattr(col.values, "dtype", type(col.values).__name__)
            raise ScoreSchemaError(
                f"column '{nm}': values of dtype {dt} cannot convert to "
                f"float32 for the fused serve program ({e})") from e
        want = expected_shapes.get(nm)
        if want is not None and tuple(v.shape[1:]) != want:
            raise ScoreSchemaError(
                f"column '{nm}': per-row shape {tuple(v.shape[1:])} does "
                f"not match the fitted schema {want}")
        expected_shapes.setdefault(nm, tuple(v.shape[1:]))
        return col

    def score(table: FeatureTable) -> FeatureTable:
        missing = [nm for nm in required_external
                   if nm not in table.column_names]
        if missing:
            raise ScoreSchemaError(
                f"input is missing column(s) {missing} required by the "
                f"fitted model; table has {sorted(table.column_names)}")
        plan = _plan.get_plan(stages, table, keep_intermediates=False,
                              extra_keep=result_names, cat="score")
        if plan is None:       # planning off / chaos / nothing to fuse
            return model.score(table=table)
        for nm in plan.device_table_inputs(table):
            # validate BEFORE any jit sees the batch
            _validated_input(table, nm)
        out = _plan.apply_planned(stages, table, keep_intermediates=False,
                                  extra_keep=result_names, cat="score")
        if out is None:        # planned run raised; recorded → eager
            return model.score(table=table)
        return out

    return score


def serve_table_builder(model) -> Callable[[Sequence[Dict[str, Any]]], FeatureTable]:
    """The serve-time table front: ``build(rows) -> FeatureTable`` running
    each raw feature's extract over the request rows. Shared by
    :func:`micro_batch_score_function`, the serving runtime
    (``serving/runtime.py``), and the warm-start plan fingerprint
    (``serving/warmup.py``) — all three must build byte-identical tables or
    the fingerprinted plan cache would miss on the first real request.

    Homogeneous numeric batches — the overwhelmingly common serve shape —
    take a vectorized path: plain-field extractors gather with one dict
    lookup per cell and convert through ``table.column_of_scalars`` (one
    numpy sweep) instead of ``Column.of_values``'s per-cell python loop;
    anything non-homogeneous (a None, a string, a custom extractor) falls
    back to the exact original path, so outputs are byte-identical."""
    from ..readers.readers import _field_name_of
    from ..table import column_of_scalars
    raw_features = model.raw_features
    #: (feature, plain record field to gather, or None → stage.extract)
    extractors = [(f, _field_name_of(f.origin_stage.extract_fn))
                  for f in raw_features]

    def build(rows: Sequence[Dict[str, Any]]) -> FeatureTable:
        cols = {}
        dict_rows = all(isinstance(r, dict) for r in rows)
        for f, field in extractors:
            col = None
            if field is not None and dict_rows:
                # fast gather skips the FeatureType-unwrap extract() makes;
                # a wrapper (or any non-scalar) fails the numpy sweep and
                # re-extracts below, so semantics never diverge
                col = column_of_scalars(
                    f.feature_type, [r.get(field) for r in rows])
            if col is None:
                vals = [f.origin_stage.extract(r) for r in rows]
                col = column_of_scalars(f.feature_type, vals)
            if col is None:
                try:
                    col = Column.of_values(f.feature_type, vals)
                except (TypeError, ValueError) as e:
                    raise ScoreSchemaError(
                        f"raw feature '{f.name}' ({f.type_name}): value "
                        f"does not conform to the fitted schema "
                        f"({type(e).__name__}: {e})") from e
            cols[f.name] = col
        return FeatureTable(cols, len(rows))

    return build


def serve_record_builder(model) -> Callable[[FeatureTable, int], List[Dict[str, Any]]]:
    """``records(scored_table, n) -> [result dict]`` — the serve-time
    row-major view of a scored table (Prediction columns as {key: float}
    maps, masked slots as None)."""
    result_features = model.result_features

    def records(scored: FeatureTable, n: int) -> List[Dict[str, Any]]:
        # columnar → row-major in one ``tolist()`` C sweep per column
        # (identical python values: tolist() and .item() both produce the
        # nearest python float/int), instead of a numpy scalar indexing +
        # .item() round-trip per cell — with the table build, this was the
        # serve hot path
        per_col: List[Tuple[str, Optional[list], list, Optional[Tuple]]] = []
        for f in result_features:
            col = scored[f.name]
            masks = None if col.mask is None else \
                np.asarray(col.mask).tolist()
            vals = np.asarray(col.values).tolist()
            keys = (tuple(col.metadata.get("keys", ()))
                    if f.type_name == "Prediction" else None)
            per_col.append((f.name, masks, vals, keys))
        out: List[Dict[str, Any]] = []
        for i in range(n):
            rec: Dict[str, Any] = {}
            for name, masks, vals, keys in per_col:
                if masks is not None and not masks[i]:
                    rec[name] = None
                elif keys is not None:
                    rec[name] = dict(zip(keys, vals[i]))
                else:
                    rec[name] = vals[i]
            out.append(rec)
        return out

    return records


class ServeStages:
    """Staged decomposition of :func:`micro_batch_score_function` for the
    pipelined serving dataplane (serving/runtime.py; docs/serving.md
    "Pipelined dataplane"). The monolithic scorer runs
    build → compile → flatten back-to-back on one thread; the pipeline
    needs the same three steps as separable stages so batch formation,
    device dispatch, and result resolution can overlap across flushes:

    * :meth:`gather` — request rows → FeatureTable, one columnar sweep
      per raw feature through **pooled per-bucket scratch blocks**: the
      per-flush gather list (``[r.get(field) for r in rows]``) is
      replaced by an object-dtype scratch array reused across flushes
      (grown to the enclosing power-of-two bucket, mirroring the plan
      padding buckets), so the steady state allocates nothing per flush.
      ``column_of_scalars`` reads the scratch through a numpy view and
      materializes fresh output arrays, so reuse is invisible; any
      non-homogeneous column falls back to the full
      :func:`serve_table_builder` path — byte-identical by construction.
    * :meth:`dispatch` — launch the compiled program. JAX dispatch is
      asynchronous: the returned table holds device arrays whose math may
      still be running, so the caller can start gathering the next flush.
    * :meth:`flatten` — block on the device results and produce exactly
      the records :func:`serve_record_builder` emits.

    Failure semantics stay with the caller: the serving runtime reproduces
    the monolithic scorer's quarantine fallback by re-scoring a failed
    flush through ``micro_batch_score_function`` itself, so pipelined
    records are bit-equal to serial ones on every path."""

    def __init__(self, model):
        from ..readers.readers import _field_name_of
        self._build = serve_table_builder(model)
        self.dispatch = compiled_score_function(model)
        self.flatten = serve_record_builder(model)
        self._extractors = [(f, _field_name_of(f.origin_stage.extract_fn))
                            for f in model.raw_features]
        #: per-feature pooled scratch (object dtype; single-thread use —
        #: the batcher owns the gather stage)
        self._scratch: Dict[str, np.ndarray] = {}

    def gather(self, rows: Sequence[Dict[str, Any]]) -> FeatureTable:
        from ..table import column_of_scalars
        n = len(rows)
        if not n or not all(isinstance(r, dict) for r in rows):
            return self._build(rows)
        cols: Dict[str, Column] = {}
        for f, field in self._extractors:
            col = None
            if field is not None:
                buf = self._scratch.get(f.name)
                if buf is None or buf.shape[0] < n:
                    # grow to the enclosing bucket so one block serves
                    # every flush size up to max_batch
                    cap = max(64, 1 << (n - 1).bit_length())
                    buf = np.empty(cap, dtype=object)
                    self._scratch[f.name] = buf
                for i, r in enumerate(rows):
                    buf[i] = r.get(field)
                col = column_of_scalars(f.feature_type, buf[:n])
            if col is None:
                # a wrapper/None/string (or a custom extractor) broke the
                # fast sweep: rebuild the WHOLE table through the original
                # path so the result is identical to the serial builder
                return self._build(rows)
            cols[f.name] = col
        return FeatureTable(cols, n)


def micro_batch_score_function(model) -> Callable[[Sequence[Dict[str, Any]]], List[Dict[str, Any]]]:
    """Micro-batch scorer: builds a FeatureTable from a list of raw rows and
    runs the columnar/jitted DAG pass — the serving path that keeps the TPU
    busy (SURVEY §2.10 P4: streaming micro-batches). The numeric transformer
    tail runs as one compiled XLA program per device-fusable segment,
    reused across micro-batch sizes via the schema-fingerprinted plan
    cache (compiled_score_function → plan.py; docs/plan.md). For driving
    this under concurrent load — continuous batching, deadlines, a
    circuit breaker — see ``transmogrifai_tpu/serving`` (docs/serving.md).

    Malformed input does not kill the batch: a batch that fails schema
    validation (a string where a number is expected, a wrong-width vector)
    falls back to per-row scoring, and only the offending rows are
    **quarantined** — their result features come back None with the reason
    under :data:`SCORE_ERROR_KEY` — while every valid row still scores."""
    result_features = model.result_features
    compiled = compiled_score_function(model)
    _build_table = serve_table_builder(model)
    _records = serve_record_builder(model)

    def score(rows: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
        t0 = time.perf_counter()
        quarantined = 0
        with _obs_span("score.micro_batch", cat="score",
                       rows=len(rows)) as sp:
            try:
                out = _records(compiled(_build_table(rows)), len(rows))
            except (ScoreSchemaError, TypeError, ValueError) as batch_err:
                # isolate the offenders: score each row alone; rows that
                # still fail are quarantined instead of poisoning the batch
                out = []
                for row in rows:
                    try:
                        out.append(
                            _records(compiled(_build_table([row])), 1)[0])
                    except (ScoreSchemaError, TypeError, ValueError) as e:
                        rec = {f.name: None for f in result_features}
                        rec[SCORE_ERROR_KEY] = str(e) or str(batch_err)
                        out.append(rec)
                        quarantined += 1
                sp.add_event("score.quarantine", rows=quarantined,
                             batchError=str(batch_err)[:200])
                logger.warning(
                    "micro-batch scoring quarantined %d/%d row(s) "
                    "(first batch error: %s)", quarantined, len(rows),
                    batch_err)
        if _obs_metrics.metrics_enabled():
            # per-micro-batch latency + row/quarantine counters: the serve
            # path's p50/p95/p99 surfaces in summary()["observability"]
            # and metrics.prom (docs/observability.md)
            _obs_metrics.observe(
                "tg_score_microbatch_seconds", time.perf_counter() - t0,
                help="per-micro-batch scoring latency (columnar path)")
            _obs_metrics.inc_counter(
                "tg_score_rows_total", float(len(rows)),
                help="rows submitted to micro-batch scoring")
            if quarantined:
                _obs_metrics.inc_counter(
                    "tg_score_quarantined_total", float(quarantined),
                    help="rows quarantined under __score_error__")
        return out

    return score
