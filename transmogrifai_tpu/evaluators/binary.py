"""Binary classification evaluators (reference:
core/.../evaluators/OpBinaryClassificationEvaluator.scala,
OpBinScoreEvaluator.scala)."""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..ops.metrics import (
    aupr, aupr_masked, auroc, auroc_masked, binary_confusion,
    log_loss, log_loss_masked, threshold_metrics,
)
from ..table import FeatureTable
from .base import OpEvaluatorBase, pad_rows_to_bucket


class OpBinaryClassificationEvaluator(OpEvaluatorBase):
    """Precision/Recall/F1/AuROC/AuPR/Error + confusion + threshold curves
    (reference OpBinaryClassificationEvaluator.evaluateAll:68)."""

    default_metric = "AuPR"
    larger_better = True

    def __init__(self, num_threshold_bins: int = 100, **kw):
        super().__init__(**kw)
        self.num_threshold_bins = num_threshold_bins

    def evaluate_parts(self, label, parts, mask=None) -> Dict[str, float]:
        # rows bucket-padded (mask False, score below every threshold) so
        # the metric programs are shared across dataset sizes
        label, parts, mask = pad_rows_to_bucket(label, parts, mask)
        prob = parts.get("probability")
        scores = prob[:, 1] if prob is not None and prob.shape[1] > 1 else \
            parts["prediction"]
        mask = jnp.asarray(mask)
        return self._metrics(
            jnp.where(mask, jnp.asarray(label, jnp.float32), 0.0),
            jnp.where(mask, jnp.asarray(scores, jnp.float32), -1.0), mask)

    def evaluate_arrays(self, label, scores, probability=None) -> float:
        s = probability if probability is not None else scores
        return float(aupr(jnp.asarray(s), jnp.asarray(label)))

    def _metrics(self, label, scores, mask) -> Dict[str, float]:
        w = mask.astype(scores.dtype)
        pred = (scores >= 0.5).astype(scores.dtype) * w
        pos = (label > 0.5).astype(scores.dtype) * w
        tp = float((pred * pos).sum())
        fp = float((pred * (w - pos)).sum())
        fn = float(((w - pred) * pos).sum())
        tn = float(w.sum()) - tp - fp - fn
        precision = tp / (tp + fp) if tp + fp > 0 else 0.0
        recall = tp / (tp + fn) if tp + fn > 0 else 0.0
        f1 = 2 * precision * recall / (precision + recall) if precision + recall > 0 else 0.0
        n = tp + tn + fp + fn
        # padded rows score -1 → never >= any threshold in [0, 1]
        thr, p_curve, r_curve, f1_curve = threshold_metrics(
            scores, label, num_bins=self.num_threshold_bins)
        return {
            "Precision": precision, "Recall": recall, "F1": f1,
            "AuROC": float(auroc_masked(scores, label, mask)),
            "AuPR": float(aupr_masked(scores, label, mask)),
            "Error": (fp + fn) / n if n > 0 else 0.0,
            "TP": tp, "TN": tn, "FP": fp, "FN": fn,
            "LogLoss": float(log_loss_masked(scores, label, mask)),
            "thresholds": np.asarray(thr).tolist(),
            "precisionByThreshold": np.asarray(p_curve).tolist(),
            "recallByThreshold": np.asarray(r_curve).tolist(),
            "f1ByThreshold": np.asarray(f1_curve).tolist(),
        }


class OpBinScoreEvaluator(OpEvaluatorBase):
    """Calibration-bin metrics (reference OpBinScoreEvaluator.scala): score
    bins → average score vs conversion rate, plus Brier score."""

    default_metric = "BrierScore"
    larger_better = False

    def __init__(self, num_bins: int = 100, **kw):
        super().__init__(**kw)
        self.num_bins = num_bins

    def evaluate_all(self, table: FeatureTable) -> Dict[str, float]:
        label, parts = self._extract(table)
        prob = parts.get("probability")
        scores = prob[:, 1] if prob is not None and prob.shape[1] > 1 else \
            parts["prediction"]
        scores = np.asarray(scores, dtype=np.float64)
        label = np.asarray(label, dtype=np.float64)
        bins = np.clip((scores * self.num_bins).astype(int), 0, self.num_bins - 1)
        counts = np.bincount(bins, minlength=self.num_bins).astype(np.float64)
        score_sum = np.bincount(bins, weights=scores, minlength=self.num_bins)
        label_sum = np.bincount(bins, weights=label, minlength=self.num_bins)
        nz = np.maximum(counts, 1.0)
        return {
            "BrierScore": float(((scores - label) ** 2).mean()),
            "binCenters": ((np.arange(self.num_bins) + 0.5) / self.num_bins).tolist(),
            "numberOfDataPoints": counts.tolist(),
            "averageScore": (score_sum / nz).tolist(),
            "averageConversionRate": (label_sum / nz).tolist(),
        }
