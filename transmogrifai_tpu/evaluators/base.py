"""Evaluator bases (reference: core/.../evaluators/OpEvaluatorBase.scala:113-235).

Evaluators read a fitted Prediction column — stored columnar as an (n, k)
float array with a ``keys`` tuple — plus the label column, and compute metric
dicts with jitted kernels.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..features import Feature
from ..table import Column, FeatureTable
from ..types import Prediction
from ..utils.padding import bucket_for, pad_rows, padded_valid_mask


def prediction_parts(col: Column) -> Dict[str, np.ndarray]:
    """Split a prediction column into prediction / probability / rawPrediction
    arrays (the analog of the reference's flattening of the Prediction map
    into columns, OpEvaluatorBase.scala:186-235)."""
    keys = tuple(col.metadata.get("keys", ()))
    vals = np.asarray(col.values)
    if not keys:
        # plain scalar column used as a prediction
        return {"prediction": vals.reshape(len(col))}
    out: Dict[str, Any] = {}
    key_idx = {k: i for i, k in enumerate(keys)}
    if Prediction.PredictionName in key_idx:
        out["prediction"] = vals[:, key_idx[Prediction.PredictionName]]
    for prefix in (Prediction.ProbabilityName, Prediction.RawPredictionName):
        idxs = sorted(
            ((int(k.rsplit("_", 1)[1]), i) for k, i in key_idx.items()
             if k.startswith(prefix + "_")),
        )
        if idxs:
            out[prefix] = vals[:, [i for _, i in idxs]]
    return out


class OpEvaluatorBase(abc.ABC):
    """Base evaluator: binds label/prediction feature names
    (reference OpEvaluatorBase.scala:113-180)."""

    #: the single metric used for model selection
    default_metric: str = ""
    #: larger-is-better for the default metric?
    larger_better: bool = True

    def __init__(self, label_col: Optional[str] = None,
                 prediction_col: Optional[str] = None):
        self.label_col = label_col
        self.prediction_col = prediction_col

    def set_label_col(self, feature_or_name) -> "OpEvaluatorBase":
        self.label_col = getattr(feature_or_name, "name", feature_or_name)
        return self

    def set_prediction_col(self, feature_or_name) -> "OpEvaluatorBase":
        self.prediction_col = getattr(feature_or_name, "name", feature_or_name)
        return self

    def _extract(self, table: FeatureTable) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        if self.label_col is None or self.prediction_col is None:
            raise ValueError("evaluator needs label_col and prediction_col")
        label = np.asarray(table[self.label_col].values, dtype=np.float32).reshape(-1)
        parts = prediction_parts(table[self.prediction_col])
        return label, parts

    def evaluate_all(self, table: FeatureTable) -> Dict[str, float]:
        """Compute all metrics for this evaluator: :meth:`evaluate_parts`
        of the table's label and prediction columns. An evaluator states its
        metrics in ``evaluate_parts``; one that overrides this method instead
        is handed whole tables (see :func:`evaluates_parts`)."""
        return self.evaluate_parts(*self._extract(table))

    def evaluate_parts(self, label, parts: Dict[str, Any],
                       mask=None) -> Dict[str, float]:
        """All metrics from the label (n,) and the prediction parts
        (``prediction`` (n,), ``probability`` / ``rawPrediction`` (n, k)) as
        arrays, on the host or on the device. With ``mask`` the arrays are
        already padded to a row bucket and ``mask`` is False on the padding;
        without, they are padded here (:func:`pad_rows_to_bucket`), so that
        the metric programs are shared across dataset sizes."""
        raise NotImplementedError(
            f"{type(self).__name__} states its metrics in evaluate_all")

    def evaluate(self, table: FeatureTable) -> float:
        """The single default metric (used by ModelSelector)."""
        return float(self.evaluate_all(table)[self.default_metric])

    def evaluate_arrays(self, label: np.ndarray, scores: np.ndarray,
                        probability: Optional[np.ndarray] = None) -> float:
        """Array-level fast path used inside CV loops (no table plumbing)."""
        raise NotImplementedError


def evaluates_parts(evaluator) -> bool:
    """True where ``evaluator.evaluate_all(table)`` is the base's extraction
    followed by the evaluator's own ``evaluate_parts``, so that a caller
    holding label and prediction parts as arrays (the selector, on the
    device) may skip the table. An evaluator that defines only
    ``evaluate_all(table)``, or overrides it, keeps being handed tables."""
    cls = type(evaluator)
    return (getattr(cls, "evaluate_all", None) is OpEvaluatorBase.evaluate_all
            and cls.evaluate_parts is not OpEvaluatorBase.evaluate_parts)


def pad_rows_to_bucket(label, parts: Dict[str, Any], mask=None):
    """(label, parts, mask) with every array padded by zero rows to the row
    bucket of its length and ``mask`` False on the padding. Arrays that come
    with a ``mask`` are padded already and pass through."""
    if mask is not None:
        return label, parts, mask
    n = len(label)
    n_pad = bucket_for(n)
    return (pad_rows(label, n_pad),
            {k: pad_rows(v, n_pad) for k, v in parts.items()},
            padded_valid_mask(None, n, n_pad))
