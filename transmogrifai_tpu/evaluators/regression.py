"""Regression evaluator (reference:
core/.../evaluators/OpRegressionEvaluator.scala)."""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from ..ops.metrics import regression_metrics, regression_metrics_masked
from .base import OpEvaluatorBase, pad_rows_to_bucket


class OpRegressionEvaluator(OpEvaluatorBase):
    """RMSE/MSE/MAE/R² (reference OpRegressionEvaluator.scala:107)."""

    default_metric = "RootMeanSquaredError"
    larger_better = False

    def evaluate_parts(self, label, parts, mask=None) -> Dict[str, float]:
        label, parts, mask = pad_rows_to_bucket(label, parts, mask)
        return {k: float(v) for k, v in regression_metrics_masked(
            jnp.asarray(parts["prediction"], jnp.float32).reshape(-1),
            jnp.asarray(label, jnp.float32), jnp.asarray(mask)).items()}

    def evaluate_arrays(self, label, scores, probability=None) -> float:
        return float(regression_metrics(
            jnp.asarray(scores), jnp.asarray(label))["RootMeanSquaredError"])
