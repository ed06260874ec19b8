"""ModelSelector — automated model selection.

TPU re-design of the reference ModelSelector
(reference: core/.../impl/selector/ModelSelector.scala:135-196 fit flow,
:216-255 SelectedModel; ModelSelectorSummary.scala): splitter prepares the
train data (balance/cut), the validator sweeps families × grids × folds as
vmapped device batches, the winner refits on the full prepared train set, and
the fitted SelectedModel emits a Prediction column.
"""
from __future__ import annotations

import os

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...histeng import engine_mesh
from ...models.api import MODEL_REGISTRY, FittedParams, ModelFamily
from ...observability.trace import span as _obs_span
from ...parallel.distributed import _count_transfer_bytes
from ...robustness import faults
from ...robustness.guards import (
    AllCandidatesFailedError, params_finite, quarantine_non_finite,
)
from ...robustness.policy import FaultLog, FaultReport
from ...stages.base import AllowLabelAsInput, Estimator, Transformer
from ...table import Column, FeatureTable
from ...types import OPVector, Prediction, RealNN
from ...evaluators.base import evaluates_parts
from ..tuning.splitters import (
    DataSplitter, PreparedData, Splitter, label_index,
)
from ..tuning.validators import BestEstimator, OpCrossValidation, OpValidator
from ...utils.padding import bucket_for, pad_rows, padded_valid_mask

#: refit-fallback depth: how many ranked candidates may be tried when the
#: winner's full-data refit diverges before the train aborts aggregated
_MAX_REFIT_ATTEMPTS = 3


@dataclass
class ModelSelectorSummary:
    """(reference ModelSelectorSummary.scala:308)"""
    validation_type: str
    validation_metric: str
    problem: str
    best_model_type: str
    best_hyper: Dict[str, Any]
    best_metric_value: float
    larger_better: bool = True
    validation_results: List[Any] = field(default_factory=list)
    train_evaluation: Dict[str, Any] = field(default_factory=dict)
    holdout_evaluation: Dict[str, Any] = field(default_factory=dict)
    splitter_summary: Dict[str, Any] = field(default_factory=dict)
    #: validator's per-config validation-row cap (None = exact). Surfaced so
    #: a selection difference vs the reference's full-row scoring is
    #: explainable from the summary alone (the reference always scores every
    #: validation row, OpValidator.scala:270-312).
    validation_eval_row_cap: Optional[int] = None
    #: candidates excluded from selection (non-finite CV metrics, fits that
    #: threw, non-finite refit params), with their failure reasons — the
    #: sweep continued without them (docs/robustness.md)
    quarantined: List[Dict[str, Any]] = field(default_factory=list)

    def to_json(self) -> Dict[str, Any]:
        return {
            "validationType": self.validation_type,
            "validationMetric": self.validation_metric,
            "problem": self.problem,
            "bestModelType": self.best_model_type,
            "bestHyperparameters": self.best_hyper,
            "bestMetricValue": self.best_metric_value,
            "largerBetter": self.larger_better,
            "validationResults": [r.to_json() for r in self.validation_results],
            "trainEvaluation": self.train_evaluation,
            "holdoutEvaluation": self.holdout_evaluation,
            "splitterSummary": self.splitter_summary,
            "validationEvalRowCap": self.validation_eval_row_cap,
            "quarantinedCandidates": [dict(r) for r in self.quarantined],
        }


class ModelSelector(AllowLabelAsInput, Estimator):
    """Estimator[(RealNN label, OPVector features)] → Prediction."""

    input_types = (RealNN, OPVector)
    output_type = Prediction

    def __init__(self, problem: str,
                 validator: Optional[OpValidator] = None,
                 splitter: Optional[Splitter] = None,
                 models: Optional[Sequence[Tuple[Any, Optional[List[Dict[str, Any]]]]]] = None,
                 evaluator=None,
                 uid: Optional[str] = None):
        super().__init__("modelSelector", uid)
        if problem not in ("binary", "multiclass", "regression"):
            raise ValueError(f"unknown problem kind '{problem}'")
        self.problem = problem
        self.validator = validator or OpCrossValidation()
        self.splitter = splitter if splitter is not None else DataSplitter()
        self.evaluator = evaluator
        self.models = self._resolve_models(models)
        self.mesh = None

    def set_mesh(self, mesh) -> "ModelSelector":
        """Shard the sweep over a ('data', 'model') mesh: rows over 'data',
        the config batch over 'model' (SURVEY §2.10 P1/P2; the reference's
        8-thread Future pool becomes mesh axes). Also shards the winner
        refit, and the fitted SelectedModel keeps scoring row-sharded (the
        train/holdout evaluations ride it)."""
        self.validator.mesh = mesh
        self.mesh = mesh
        return self

    def set_sweep_checkpoint(self, ckpt) -> "ModelSelector":
        """Preemption-tolerant sweeps (wired by ``with_checkpoint_dir``):
        every evaluated candidate batch persists its fold metrics to the
        given :class:`~...impl.tuning.sweep_checkpoint.SweepCheckpoint` as
        it completes, and a resumed ``train()`` replays the persisted
        records — fingerprint-matched to the data, folds, and sweep config
        — instead of re-running them (docs/robustness.md "Resumable
        sweeps"). Train-time wiring only; never serialized with the fitted
        model."""
        self.validator.sweep_checkpoint = ckpt
        return self

    def _resolve_models(self, models):
        resolved: List[Tuple[ModelFamily, List[Dict[str, Any]]]] = []
        from ...models import glm, trees  # noqa: F401 (registers families)
        if models is None:
            # reference default model types (BinaryClassificationModelSelector
            # Defaults.modelTypesToUse :59-61, MultiClassification :59-61,
            # RegressionModelSelector :59-61; NB/DT/XGB off by default)
            defaults = {
                "binary": ["OpLogisticRegression", "OpRandomForestClassifier",
                           "OpGBTClassifier", "OpLinearSVC"],
                "multiclass": ["OpLogisticRegression",
                               "OpRandomForestClassifier"],
                "regression": ["OpLinearRegression", "OpRandomForestRegressor",
                               "OpGBTRegressor",
                               "OpGeneralizedLinearRegression"],
            }[self.problem]
            models = [(MODEL_REGISTRY[name], None) for name in defaults]
        for fam, grid in models:
            if isinstance(fam, str):
                fam = MODEL_REGISTRY[fam]
            if self.problem not in fam.supports:
                raise ValueError(
                    f"{fam.name} does not support problem kind '{self.problem}'")
            if grid is None:
                grid = fam.default_grid(self.problem)
                # test-time knob: shrink DEFAULT grids so CPU CI suites stay
                # fast; explicitly-passed grids are never touched. Env (not a
                # fixture) because the CLI test's generated app runs in a
                # subprocess. Loud, so a leaked env can't silently degrade a
                # real AutoML run.
                if os.environ.get("TG_FAST_GRIDS", "").lower() in ("1", "true"):
                    import logging
                    logging.getLogger(__name__).warning(
                        "TG_FAST_GRIDS is set: default %s grid truncated "
                        "%d -> 2 configs (test mode)", fam.name, len(grid))
                    grid = grid[:2]
            resolved.append((fam, grid))
        return resolved

    @property
    def validation_metric(self) -> Tuple[str, bool]:
        if self.evaluator is not None:
            return self.evaluator.default_metric, self.evaluator.larger_better
        return {"binary": ("AuPR", True),
                "multiclass": ("F1", True),
                "regression": ("RootMeanSquaredError", False)}[self.problem]

    # -- workflow-level CV (reference findBestEstimator :112-121) ------------
    def find_best_estimator(self, table: FeatureTable,
                            during_layers: Sequence[Sequence[Tuple[Any, int]]],
                            ) -> BestEstimator:
        """Leakage-free validation: per fold, fit fresh copies of the in-CV
        DAG (label-dependent prep like SanityChecker) on the fold's train rows
        only, then sweep the model grid on the fold-specific feature matrix
        (reference OpValidator.applyDAG :228-256 + getSummary). The winner is
        recorded; the subsequent normal ``fit`` skips validation and refits it
        on the full prepared data (reference OpWorkflow.fitStages :397-442)."""
        label_f, vec_f = self.input_features
        y_all = np.asarray(table[label_f.name].values,
                           dtype=np.float32).reshape(-1)
        n = len(y_all)
        # reserve the SAME holdout the later fit() will carve out (splitter
        # split is seed-deterministic in n), so selection never sees it
        if self.splitter is not None and self.splitter.reserve_test_fraction > 0:
            train_idx, _ = self.splitter.split(n)
        else:
            train_idx = np.arange(n)
        y_train_raw = y_all[train_idx]
        prep = (self.splitter.pre_validation_prepare(y_train_raw)
                if self.splitter is not None
                else PreparedData(indices=np.arange(len(y_train_raw))))
        sel_rows = train_idx[prep.indices]
        sub = table.take(sel_rows)
        y = y_all[sel_rows]
        labels = label_index(prep.label_mapping)
        if labels is not None:
            y = labels.forward(y)
        num_classes = int(y.max()) + 1 if self.problem != "regression" else 1
        if self.problem == "binary":
            num_classes = 2
        metric_name, larger_better = self.validation_metric

        val_masks = self.validator.make_splits(y)          # (F, n)
        F = val_masks.shape[0]
        # pass 1: fit every fold's in-CV DAG copy and collect its feature
        # matrix (fold-specific SanityCheckers may keep different columns).
        # Stage-by-stage across folds: each estimator's F fold fits are
        # QUEUED via the fit_queued protocol and resolved with one fused
        # host transfer (stages/base.materialize_pending) — the fold-serial
        # host loop's F sync round-trips were the residual wall over plain
        # CV (reference fits fold DAG copies on concurrent Futures,
        # OpValidator.applyDAG :228-256). Matrices park on HOST between
        # passes — holding F device copies would multiply peak HBM by the
        # fold count at 1M×543 scale
        from ...stages.base import materialize_pending
        fold_train_rows = [np.nonzero(~val_masks[f])[0] for f in range(F)]
        fold_tbls: List[Any] = [sub] * F
        for layer in during_layers:
            for stage, _ in layer:
                if isinstance(stage, Estimator):
                    # fit on each fold's train rows only; one transform of
                    # the full table serves both train and val rows
                    pend = [stage.fit_queued(
                        fold_tbls[f].take(fold_train_rows[f]))
                        for f in range(F)]
                    stage_models = materialize_pending(pend)
                else:
                    stage_models = [stage] * F
                for f in range(F):
                    fold_tbls[f] = stage_models[f].transform(fold_tbls[f])
        fold_X: List[Optional[np.ndarray]] = []
        for f in range(F):
            if vec_f.name not in fold_tbls[f].column_names:
                raise ValueError(
                    f"in-CV DAG did not produce feature '{vec_f.name}'")
            fold_X.append(np.asarray(fold_tbls[f][vec_f.name].values,
                                     dtype=np.float32))
        del fold_tbls
        # pass 2: pad every fold's matrix to the widest fold with zero
        # columns (inert: dead-column standardization pins their linear
        # coefficients to 0, constant columns never win a tree split), so
        # all F validates share ONE compiled program per family instead of
        # paying a full compile per fold-specific width (reference
        # OpValidator.applyDAG :228-256 fits fold DAG copies concurrently;
        # here the concurrency win is amortized compilation + queued device
        # programs)
        d_max = max(x.shape[1] for x in fold_X)
        yd = jnp.asarray(y)
        # when all folds' matrices fit on device together, queue EVERY
        # fold's validate programs back-to-back and sync ONCE at the end
        # (resolve=False) — the fold-serial host loop was the residual 1.75x
        # over plain CV; at larger scales matrices park on host and each
        # fold resolves before the next uploads, bounding peak HBM to one
        # fold matrix (reference fits fold DAG copies on concurrent
        # Futures, OpValidator.applyDAG :228-256)
        defer = F * val_masks.shape[1] * d_max * 4 <= (2 << 30)
        fold_results: List[Any] = []
        for f in range(F):
            Xh = fold_X[f]
            fold_X[f] = None          # drop the host ref once uploaded
            if Xh.shape[1] != d_max:
                Xh = np.pad(Xh, ((0, 0), (0, d_max - Xh.shape[1])))
            fold_results.append(self.validator.validate(
                self.models, jnp.asarray(Xh), yd, self.problem, metric_name,
                larger_better, num_classes, val_masks=val_masks[f][None, :],
                resolve=not defer))
        fold_results = [r.resolve() if hasattr(r, "resolve") else r
                        for r in fold_results]

        # average fold winners per (family, grid point); a candidate with a
        # non-finite metric in ANY fold has a non-finite mean and is
        # quarantined from the merged selection (guards; the per-fold
        # validates already recorded the fold-level reports)
        best: Optional[BestEstimator] = None
        merged: List[Any] = []
        quarantined: List[Dict[str, Any]] = []
        for i, (family, grid) in enumerate(self.models):
            folds = np.stack([fr.results[i].fold_metrics[0]
                              for fr in fold_results])      # (F, G)
            r = fold_results[0].results[i]
            mean, masked, records = quarantine_non_finite(
                family.name, list(grid), folds, metric_name, larger_better)
            quarantined.extend(records)
            r.fold_metrics, r.mean_metrics = folds, mean
            merged.append(r)
            if not np.isfinite(mean).any():
                continue
            g_best = int(np.argmax(masked) if larger_better
                         else np.argmin(masked))
            value = float(mean[g_best])
            if best is None or ((value > best.metric_value) if larger_better
                                else (value < best.metric_value)):
                best = BestEstimator(family.name, dict(grid[g_best]), value)
        if best is None:
            raise AllCandidatesFailedError(quarantined)
        best.results = merged
        best.quarantined = quarantined
        self._preset_best = best
        return best

    # -- fit (reference ModelSelector.fit :135-196) --------------------------
    def fit(self, table: FeatureTable) -> Transformer:
        label_f, vec_f = self.input_features
        # label preparation, the split, and the one upload of the feature
        # matrix and labels: everything before the sweep
        with _obs_span("selector.prepare", cat="train",
                       hbm=True) as prepare_span:
            with _obs_span("prepare.labels") as step:
                y_all = np.asarray(table[label_f.name].values,
                                   dtype=np.float32).reshape(-1)
                # the feature matrix never visits the host again: row
                # selections for the holdout/balancer are index gathers on
                # device
                vec = table[vec_f.name].values
                Xd_all = jnp.asarray(vec, dtype=jnp.float32)
                if isinstance(vec, np.ndarray):
                    _count_transfer_bytes(Xd_all, "h2d")
                n = len(y_all)
                step.set_attr(rows=n)

            # reserve holdout (reference splitter.split in workflow fitStages)
            with _obs_span("prepare.split", rows=n) as step:
                if (self.splitter is not None
                        and self.splitter.reserve_test_fraction > 0):
                    train_idx, test_idx = self.splitter.split(n)
                else:
                    train_idx = np.arange(n)
                    test_idx = np.array([], dtype=np.int64)
                step.set_attr(trainRows=len(train_idx),
                              testRows=len(test_idx))

            with _obs_span("prepare.balance",
                           splitter=type(self.splitter).__name__,
                           rowsIn=len(train_idx)) as step:
                y_train_raw = y_all[train_idx]
                prep = (self.splitter.pre_validation_prepare(y_train_raw)
                        if self.splitter is not None
                        else PreparedData(indices=np.arange(len(y_train_raw))))
                sel = train_idx[prep.indices]
                # the cutter's re-indexing, once for every row: the fit
                # reads the rows kept, the evaluation below every row of
                # the split
                labels = label_index(prep.label_mapping)
                y_dense = y_all if labels is None else labels.forward(y_all)
                step.set_attr(rowsKept=len(sel))
            prepare_span.set_attr(
                labelMap="none" if labels is None else "lookup")

            with _obs_span("prepare.gather", rows=len(sel)):
                y = y_dense[sel]
                num_classes = (int(y.max()) + 1
                               if self.problem != "regression" else 1)
                if self.problem == "binary":
                    num_classes = 2

                metric_name, larger_better = self.validation_metric
                yd = jnp.asarray(y)
                _count_transfer_bytes(yd, "h2d")
                if self.mesh is not None:
                    # shard to shard, straight into the row bucket the
                    # sweep and the refit share (rows past len(y) are
                    # zeros): no chip holds the rows kept whole, and
                    # neither pads a copy
                    from ...parallel.sharded import take_rows
                    n_b = bucket_for(len(sel),
                                     multiple_of=self.mesh.shape["data"])
                    Xd = take_rows(Xd_all,
                                   np.pad(sel, (0, n_b - len(sel)),
                                          constant_values=-1), self.mesh,
                                   site="selector.prepare")
                else:
                    sel_d = jnp.asarray(sel)
                    _count_transfer_bytes(sel_d, "h2d")
                    Xd = Xd_all[sel_d]
                    del sel_d    # an index vector, not to outlive its gather
            if "labelsKept" in prep.summary:     # what DataCutter kept
                prepare_span.set_attr(
                    labelsKept=len(prep.summary["labelsKept"]),
                    rowsKept=prep.summary["rowsKept"])
        preset = getattr(self, "_preset_best", None)
        if preset is not None:
            # workflow-level CV already ran (find_best_estimator); skip the
            # in-selector sweep and refit the recorded winner. Consume it so a
            # later refit on new data validates from scratch.
            self._preset_best = None
            best = preset
        else:
            best = self.validator.validate(
                self.models, Xd, yd, self.problem, metric_name, larger_better,
                num_classes,
                padded_rows=(int(Xd.shape[0]) if self.mesh is not None
                             else None))

        # deterministic preemption point: the sweep completed (and, under a
        # checkpoint dir, persisted) but the winner never refit — a resume
        # replays the sweep from disk and goes straight to the refit
        faults.inject("preempt.refit")

        # refit winner on full prepared train (reference :158-159); rows
        # bucket-padded with zero weights for compile reuse
        n_fit = len(y)
        n_data = self.mesh.shape["data"] if self.mesh is not None else 1
        n_pad = bucket_for(n_fit, multiple_of=n_data)
        Xf, yf = Xd, yd
        if self.mesh is not None:
            # the winner refit is a full-data fit — its rows are sharded
            # over 'data' like the sweep's: Xd came from the gather already
            # at this bucket, the label and the weight row are built on the
            # host and sent shard by shard; placements retry transient link
            # errors (robustness/policy.py)
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ...parallel.distributed import retrying_device_put
            from ...parallel.sharded import place_rows
            assert Xf.shape[0] == n_pad, (Xf.shape, n_pad)
            yf = place_rows(pad_rows(y, n_pad), self.mesh,
                            site="refit.upload")
            W = retrying_device_put(
                padded_valid_mask(None, n_fit, n_pad).astype(
                    np.float32)[None, :],
                NamedSharding(self.mesh, P(None, "data")),
                site="refit.upload")
        else:
            if n_pad != n_fit:
                Xf = jnp.pad(Xd, ((0, n_pad - n_fit), (0, 0)))
                yf = jnp.pad(yd, (0, n_pad - n_fit))
            W = jnp.zeros((1, n_pad), jnp.float32).at[:, :n_fit].set(1.0)
        # refit with a non-finite guard and fallback: a winner that diverges
        # on the full prepared train (the sweep fit at a sample/cap; the
        # refit is the exact program) is quarantined and the next-ranked
        # finite candidate refits instead. With no fault the first candidate
        # IS the sweep winner, bit-identically. params_finite fetches, so
        # the span closes where the host has waited for the winner's fit.
        with _obs_span("selector.refit", cat="train",
                       hbm=True) as refit_span:
            fitted = None
            best_used = (best.family_name, dict(best.hyper), best.metric_value)
            refit_quarantine: List[Dict[str, Any]] = []
            for fam_name, hyper, value in self._ranked_candidates(
                    best, larger_better)[:_MAX_REFIT_ATTEMPTS]:
                family = MODEL_REGISTRY[fam_name]
                try:
                    faults.inject("selector.refit", key=fam_name)
                    garr = family.grid_to_arrays([hyper])
                    # rows are 'data'-sharded under a mesh: trace the refit
                    # with the engine's sharded contractions, like the sweep
                    with engine_mesh(self.mesh):
                        params_b = family.fit_batch(Xf, yf, W, garr,
                                                    num_classes)
                    sel_params = family.select_params(params_b, 0)
                    if not params_finite(sel_params,
                                         getattr(family, "inf_ok_params", ())):
                        raise ArithmeticError(
                            "refit produced non-finite fitted params")
                    fitted = FittedParams(
                        family=fam_name, params=sel_params,
                        hyper=dict(hyper), num_classes=num_classes)
                    best_used = (fam_name, dict(hyper), value)
                    break
                except Exception as e:
                    rec = {"family": fam_name, "hyper": dict(hyper),
                           "reason": f"refit failed: {type(e).__name__}: {e}"}
                    refit_quarantine.append(rec)
                    FaultLog.record(FaultReport(site="selector.refit",
                                                kind="quarantine", detail=rec))
            with engine_mesh(self.mesh):      # as the fit was traced
                own = MODEL_REGISTRY[best_used[0]].fit_span_attrs(
                    n_pad, int(Xf.shape[1]), [best_used[1]], num_classes,
                    False)
            refit_span.set_attr(family=best_used[0],
                                attempts=(len(refit_quarantine)
                                          + (fitted is not None)),
                                classes=num_classes, lanes=1, rows=n_fit,
                                features=int(Xf.shape[1]),
                                **self._mesh_attrs(n_pad), **own)
        if fitted is None:
            raise AllCandidatesFailedError(
                list(best.quarantined) + refit_quarantine)

        summary = ModelSelectorSummary(
            validation_type=type(self.validator).__name__,
            validation_metric=metric_name,
            problem=self.problem,
            best_model_type=best_used[0],
            best_hyper=best_used[1],
            best_metric_value=best_used[2],
            larger_better=larger_better,
            validation_results=best.results,
            splitter_summary=dict(getattr(self.splitter, "summary", {}) or {}),
            validation_eval_row_cap=getattr(self.validator, "max_eval_rows",
                                            None),
            quarantined=list(best.quarantined) + refit_quarantine,
        )
        model = SelectedModel(fitted=fitted, summary=summary,
                              label_mapping=prep.label_mapping)
        model.mesh = self.mesh
        model = self._finalize_model(model)

        # train/holdout evaluation (reference :168-188). Where the winner
        # predicts on the device and the evaluator states its metrics over
        # arrays, a row is read where it already lies: the matrix of this
        # fit, the family's device predict, the metrics beside it; the host
        # gets the numbers. Else the table goes through the model's
        # transform and the evaluator as a user's would.
        with _obs_span("selector.evaluate", cat="train", hbm=True,
                       rows=len(train_idx) + len(test_idx)) as eval_span:
            ev = self._default_evaluator()
            ev.set_label_col(label_f.name)
            ev.set_prediction_col(model.get_output().name)
            on_device = model.device_fusable and evaluates_parts(ev)
            results, host_bytes = [], 0
            for split, idx in (("train", train_idx), ("holdout", test_idx)):
                if not len(idx):
                    results.append({})
                    continue
                # the split's three steps: its rows, the launch of the
                # predict, and the metrics, where the host waits for them
                said = dict(split=split, rows=len(idx),
                            path="device" if on_device else "table")
                if on_device:
                    with _obs_span("evaluate.rows", **said):
                        if (idx is train_idx
                                and np.array_equal(sel, train_idx)):
                            # nothing dropped or resampled: the refit's
                            # operands
                            X, lab, mask = Xf, yf, padded_valid_mask(
                                None, n_fit, n_pad)
                        else:
                            X, lab, mask = self._rows_on_device(
                                Xd_all, y_dense, idx)
                    family = MODEL_REGISTRY[fitted.family]
                    with _obs_span("evaluate.predict", **said) as step, \
                            engine_mesh(self.mesh):
                        parts = family.predict_parts(fitted, X)
                        step.set_attr(**family.predict_span_attrs(
                            fitted, rows=X.shape[0]))
                    with _obs_span("evaluate.metrics", **said):
                        results.append(ev.evaluate_parts(lab, parts, mask))
                    host_bytes += 4 * _count_numbers(results[-1])
                else:
                    with _obs_span("evaluate.rows", **said):
                        rows = table.take(idx)
                    with _obs_span("evaluate.predict", **said):
                        scored = model.transform(rows)
                    with _obs_span("evaluate.metrics", **said):
                        results.append(ev.evaluate_all(scored))
                    host_bytes += _host_bytes(scored)
            summary.train_evaluation, summary.holdout_evaluation = (
                _scalar_metrics(r) for r in results)
            eval_span.set_attr(
                labelMap="none" if labels is None else "lookup",
                evalPath="device" if on_device else "table",
                hostBytes=host_bytes,
                **self._mesh_attrs(len(train_idx) + len(test_idx)))
        model.summary_metadata = summary.to_json()
        return model

    def _mesh_attrs(self, rows: int) -> Dict[str, Any]:
        """The mesh a refit or an evaluation of ``rows`` rows runs on, for
        its span: unlike the sweep, neither asks the cost model."""
        from ...parallel.mesh import mesh_span_attrs
        return mesh_span_attrs(self.mesh, self.mesh is not None, rows)

    def _ranked_candidates(self, best, larger_better: bool):
        """Winner first, then every other finite-metric candidate ordered by
        mean validation metric — the refit fallback order used when the
        winner's full-data refit throws or yields non-finite params."""
        ranked = [(best.family_name, dict(best.hyper), best.metric_value)]
        pool = []
        for r in best.results or []:
            for g, hyper in enumerate(r.grid):
                v = float(r.mean_metrics[g])
                if not np.isfinite(v):
                    continue
                if (r.family == ranked[0][0] and dict(hyper) == ranked[0][1]):
                    continue
                pool.append((r.family, dict(hyper), v))
        pool.sort(key=(lambda t: -t[2]) if larger_better else (lambda t: t[2]))
        return ranked + pool

    def _rows_on_device(self, Xd_all, y_dense: np.ndarray, idx: np.ndarray):
        """(X, label, mask): rows ``idx`` of the device matrix by one gather,
        padded to a row bucket (index 0 again, mask False) and, under a
        mesh, sharded as the refit's rows are; their labels, padded alike,
        on the host."""
        n = len(idx)
        n_data = self.mesh.shape["data"] if self.mesh is not None else 1
        n_pad = bucket_for(n, multiple_of=n_data)
        idx_pad = pad_rows(idx, n_pad)
        label = pad_rows(y_dense[idx], n_pad)
        if self.mesh is not None:
            from ...parallel.sharded import take_rows
            X = take_rows(Xd_all, idx_pad, self.mesh,
                          site="selector.evaluate")
        else:
            idx_d = jnp.asarray(idx_pad)
            _count_transfer_bytes(idx_d, "h2d")
            X = Xd_all[idx_d]
        return X, label, padded_valid_mask(None, n, n_pad)

    def _default_evaluator(self):
        if self.evaluator is not None:
            return self.evaluator
        from ...evaluators import (
            OpBinaryClassificationEvaluator, OpMultiClassificationEvaluator,
            OpRegressionEvaluator)
        return {"binary": OpBinaryClassificationEvaluator,
                "multiclass": OpMultiClassificationEvaluator,
                "regression": OpRegressionEvaluator}[self.problem]()


def _scalar_metrics(metrics: Dict[str, Any]) -> Dict[str, float]:
    return {k: v for k, v in metrics.items() if isinstance(v, (int, float))}


def _count_numbers(v: Any) -> int:
    """Numbers in a metric dict (scalars, curves, count tables): what an
    evaluation on the device sends to the host, four bytes each."""
    if isinstance(v, dict):
        return sum(_count_numbers(x) for x in v.values())
    if isinstance(v, (list, tuple)):
        return sum(_count_numbers(x) for x in v)
    return 1


def _host_bytes(table: FeatureTable) -> int:
    """Bytes of a table's columns that lie on the host."""
    return sum(int(a.nbytes) for name in table.column_names
               for a in (table[name].values, table[name].mask)
               if isinstance(a, np.ndarray))


class SelectedModel(AllowLabelAsInput, Transformer):
    """The fitted winner (reference SelectedModel :216-255): emits a
    Prediction column (n, k) with keys prediction / probability_i /
    rawPrediction_i."""

    output_type = Prediction

    def __init__(self, fitted: FittedParams, summary: ModelSelectorSummary,
                 label_mapping: Optional[Dict[int, int]] = None, uid=None):
        super().__init__("modelSelector", uid)
        self.fitted = fitted
        self.summary = summary
        self.label_mapping = label_mapping
        self.summary_metadata: Dict[str, Any] = {}
        #: wiring attr (never serialized): when set, columnar scoring shards
        #: its rows over the mesh 'data' axis — the selector's train/holdout
        #: evaluations and any mesh-resident serve path ride it
        self.mesh = None

    def _unmap_prediction(self, pred: np.ndarray) -> np.ndarray:
        """Map dense class indices back to the original labels dropped/remapped
        by DataCutter (reference PredictionDeIndexer semantics; the lookup is
        ``LabelIndex.inverse``, as on the device path)."""
        labels = label_index(self.label_mapping)
        if labels is None or pred.size == 0:
            return pred
        return labels.inverse(pred)

    #: the predict is reduction-bearing (gemm / matvec / softmax): its
    #: summation order is only reproducible when X arrives as a program
    #: parameter, so the transform-plan compiler traces the Prediction
    #: emission into its OWN jitted program instead of mid-segment —
    #: keeping planned output bit-identical to the eager predict_one path
    #: (plan.py; docs/plan.md "Segment partitioning")
    device_fusion_barrier = True

    @property
    def device_fusable(self) -> bool:
        """True when the winning family has a jit-traceable predict — the
        Prediction emission then compiles into its own planned segment
        (plan.py, consumed by local/scoring.compiled_score_function;
        reference analog: the one serve pass of
        FitStagesUtil.scala:96-119)."""
        from ...models.api import ModelFamily
        family = MODEL_REGISTRY[self.fitted.family]
        return type(family).predict_parts is not ModelFamily.predict_parts

    def device_inputs(self):
        """Only the feature vector is read at serve time (the label input
        feeds training, not the fitted model)."""
        return [self.input_features[-1].name]

    def device_columnar(self, env):
        """Pure-jax dual of ``transform_column``: the (n, k) Prediction
        matrix in ``prediction_column``'s key order."""
        X, _ = env[self.device_inputs()[0]]
        family = MODEL_REGISTRY[self.fitted.family]
        parts = family.predict_parts(self.fitted, X)
        pred = parts["prediction"].reshape(-1)
        labels = label_index(self.label_mapping)
        if labels is not None:     # DataCutter de-index: _unmap_prediction
            pred = labels.inverse_device(pred)
        cols = [pred]
        for name in (Prediction.RawPredictionName,
                     Prediction.ProbabilityName):
            if name in parts:
                arr = parts[name]
                if arr.ndim == 1:
                    arr = arr[:, None]
                cols.extend(arr[:, i] for i in range(arr.shape[1]))
        return jnp.stack(cols, axis=1), None

    def transform_column(self, table: FeatureTable) -> Column:
        _, vec_f = self.input_features
        # getattr: models loaded from disk predate the wiring attr (mesh is
        # never serialized; the loading context re-attaches it if sharding)
        mesh = getattr(self, "mesh", None)
        with _obs_span("predict.pad") as step:
            X = jnp.asarray(table[vec_f.name].values, dtype=jnp.float32)
            n = X.shape[0]
            n_data = mesh.shape["data"] if mesh is not None else 1
            n_pad = bucket_for(n, multiple_of=n_data)
            if mesh is not None:
                # padded and sharded by one program: no whole copy on a chip
                from ...parallel.sharded import pad_rows_sharded
                X = pad_rows_sharded(X, n_pad, mesh)
            elif n_pad != n:  # bucket rows so the predict program is reused
                X = jnp.pad(X, ((0, n_pad - n), (0, 0)))
            step.set_attr(rows=n, paddedRows=n_pad)
        family = MODEL_REGISTRY[self.fitted.family]
        # predict_one hands back host arrays: the span holds the launch of
        # the predict and the host's wait for its parts
        with _obs_span("predict.parts", family=self.fitted.family) as step, \
                engine_mesh(mesh):
            parts = family.predict_one(self.fitted, X)
            step.set_attr(rows=n, **family.predict_span_attrs(
                self.fitted, rows=X.shape[0]))
        with _obs_span("predict.unmap"):
            if n_pad != n:
                parts = {k: v[:n] for k, v in parts.items()}
            parts = dict(parts, prediction=self._unmap_prediction(
                parts["prediction"]))
        with _obs_span("predict.column") as step:
            col = prediction_column(parts)
            step.set_attr(bytes=int(col.values.nbytes))
        return col

    def transform_row(self, row: Dict[str, Any]) -> Any:
        _, vec_f = self.input_features
        v = np.asarray(row.get(vec_f.name) or [], dtype=np.float32)[None, :]
        family = MODEL_REGISTRY[self.fitted.family]
        parts = family.predict_one(self.fitted, jnp.asarray(v))
        out = {"prediction": float(self._unmap_prediction(parts["prediction"])[0])}
        for name in ("probability", "rawPrediction"):
            if name in parts:
                for i, x in enumerate(np.asarray(parts[name][0]).reshape(-1)):
                    out[f"{name}_{i}"] = float(x)
        return out

    def summary_pretty(self) -> str:
        s = self.summary
        lines = [f"-- ModelSelector ({self.uid}) --",
                 f"Evaluated {len(s.validation_results)} model type(s) with "
                 f"{s.validation_type} on metric {s.validation_metric}",
                 f"Best model: {s.best_model_type} "
                 f"{s.best_hyper} → {s.validation_metric}={s.best_metric_value:.4f}"]
        for r in s.validation_results:
            hi, lo = np.max(r.mean_metrics), np.min(r.mean_metrics)
            b, w = (hi, lo) if s.larger_better else (lo, hi)
            lines.append(f"  {r.family}: best {b:.4f} "
                         f"worst {w:.4f} over {len(r.grid)} configs")
        if s.holdout_evaluation:
            keys = ("AuPR", "AuROC", "F1", "Error", "RootMeanSquaredError", "R2")
            show = {k: round(v, 4) for k, v in s.holdout_evaluation.items() if k in keys}
            lines.append(f"Holdout: {show}")
        if s.splitter_summary:
            lines.append(f"Splitter: {s.splitter_summary}")
        return "\n".join(lines)


def prediction_column(parts: Dict[str, np.ndarray]) -> Column:
    """Pack predict_one parts into a Prediction column."""
    n = len(parts["prediction"])
    keys: List[str] = [Prediction.PredictionName]
    cols: List[np.ndarray] = [np.asarray(parts["prediction"], dtype=np.float32).reshape(-1)]
    for name in (Prediction.RawPredictionName, Prediction.ProbabilityName):
        if name in parts:
            arr = np.asarray(parts[name], dtype=np.float32)
            if arr.ndim == 1:
                arr = arr[:, None]
            for i in range(arr.shape[1]):
                keys.append(f"{name}_{i}")
                cols.append(arr[:, i])
    mat = np.stack(cols, axis=1)
    return Column(Prediction, mat, None, {"keys": tuple(keys)})
