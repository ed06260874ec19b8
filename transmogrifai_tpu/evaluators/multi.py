"""Multiclass evaluator (reference:
core/.../evaluators/OpMultiClassificationEvaluator.scala)."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.metrics import (
    multiclass_metrics, multiclass_metrics_masked, multiclass_rank_metrics,
)
from .base import OpEvaluatorBase, pad_rows_to_bucket


class OpMultiClassificationEvaluator(OpEvaluatorBase):
    """Error / weighted Precision / Recall / F1, plus top-N threshold metrics
    (reference OpMultiClassificationEvaluator.scala; calculateThresholdMetrics
    :154-232 reduced to topK correctness curves)."""

    default_metric = "F1"
    larger_better = True

    def __init__(self, top_ns=(1, 3), thresholds=None, **kw):
        super().__init__(**kw)
        self.top_ns = tuple(top_ns)
        #: reference default: 0.0 to 1.0 by 0.1
        self.thresholds = tuple(
            thresholds if thresholds is not None
            else np.round(np.arange(0.0, 1.0001, 0.1), 2).tolist())

    def evaluate_parts(self, label, parts, mask=None) -> Dict[str, float]:
        """``label`` and ``parts["prediction"]`` are class indices of ONE
        space, the one that numbers the probability columns (the selector
        hands both over dense; a scored table carries both as the original
        labels, which is that space while the cutter's mapping is the
        identity). A label below 0, a class that has no column, counts as an
        error in Error / Precision / Recall / F1 and the top-N accuracies
        and is left out of LogLoss."""
        label, parts, mask = pad_rows_to_bucket(label, parts, mask)
        mask = jnp.asarray(mask)
        pred = jnp.asarray(parts["prediction"]).reshape(-1).astype(jnp.int32)
        label_idx = jnp.asarray(label).astype(jnp.int32)
        prob = parts.get("probability")
        top = int(jnp.max(jnp.where(mask, jnp.maximum(pred, label_idx), 0)))
        num_classes = max(top + 1, 0 if prob is None else prob.shape[1])
        # one class more than either side names: where the labels below 0 go
        out = {k: float(v) for k, v in multiclass_metrics_masked(
            pred, jnp.where(label_idx < 0, num_classes, label_idx), mask,
            num_classes + 1).items()}
        if prob is not None:
            ranked = self._rank_metrics(prob, label_idx, mask)
            out["LogLoss"] = ranked.pop("LogLoss")
            for n, hits in zip(self.top_ns, ranked.pop("hits")):
                out[f"TopN_{n}_Accuracy"] = hits / max(ranked["rows"], 1)
            out["ThresholdMetrics"] = self._threshold_tables(ranked)
        return out

    def _rank_metrics(self, prob, label_idx, mask) -> Dict[str, object]:
        """``multiclass_rank_metrics`` of float32 probabilities, fetched. A
        threshold is compared as the smallest float32 not below its float64
        value, which decides every float32 probability as the float64
        comparison would."""
        thr = np.asarray(self.thresholds, dtype=np.float64)
        thr32 = thr.astype(np.float32)
        low = thr32.astype(np.float64) < thr
        thr32[low] = np.nextafter(thr32[low], np.float32(np.inf))
        got = jax.device_get(multiclass_rank_metrics(
            jnp.asarray(prob, jnp.float32), label_idx, mask,
            jnp.asarray(thr32), self.top_ns))
        return {"LogLoss": float(got["LogLoss"]), "rows": int(got["rows"]),
                "hits": got["hits"].tolist(), "made": got["made"],
                "correct": got["correct"]}

    def _threshold_tables(self, ranked) -> Dict[str, object]:
        made, rows = ranked["made"], ranked["rows"]
        return {
            "topNs": list(self.top_ns),
            "thresholds": [float(t) for t in self.thresholds],
            "correctCounts": {n: c.tolist() for n, c
                              in zip(self.top_ns, ranked["correct"])},
            "incorrectCounts": {n: (made - c).tolist() for n, c
                                in zip(self.top_ns, ranked["correct"])},
            "noPredictionCounts": {n: (rows - made).tolist()
                                   for n in self.top_ns},
        }

    def threshold_metrics(self, prob: np.ndarray,
                          label_idx: np.ndarray) -> Dict[str, object]:
        """Per-threshold top-N correct / incorrect / no-prediction counts
        (reference calculateThresholdMetrics :154-232): a prediction is MADE
        at threshold t when max prob ≥ t; a made prediction is correct for
        topN when the true label ranks in the top N scores (ties by class
        index). Probabilities are read as float32, the Prediction column's
        dtype."""
        label_idx, parts, mask = pad_rows_to_bucket(
            np.asarray(label_idx, dtype=np.int32),
            {"probability": np.asarray(prob, dtype=np.float32)})
        return self._threshold_tables(self._rank_metrics(
            parts["probability"], jnp.asarray(label_idx), jnp.asarray(mask)))

    def evaluate_arrays(self, label, scores, probability=None) -> float:
        pred = np.asarray(scores, dtype=np.int32)
        label_idx = np.asarray(label, dtype=np.int32)
        num_classes = int(max(pred.max(initial=0), label_idx.max(initial=0))) + 1
        return float(multiclass_metrics(
            jnp.asarray(pred), jnp.asarray(label_idx), num_classes)["F1"])
