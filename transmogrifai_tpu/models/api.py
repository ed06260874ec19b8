"""Model-family API — the TPU re-design of the reference's Spark model wrappers.

The reference wraps SparkML ``Predictor``s (reference:
core/.../sparkwrappers/specific/OpPredictorWrapper.scala:67-122) and fits one
JVM job per (model, paramMap, fold). Here a *family* exposes batched, jitted
fits: ``fit_batch`` consumes stacked hyperparameters plus per-configuration
row weights and returns stacked parameters — so ModelSelector's whole
``|grid| × |folds|`` sweep compiles to ONE XLA program of MXU matmuls instead
of thousands of Spark jobs (the SURVEY §2.10 P2 axis, the north-star metric).
"""
from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from ..utils.jax_cache import ensure_compilation_cache

ensure_compilation_cache()
import numpy as np


@dataclass
class FittedParams:
    """One fitted configuration's parameters (a pytree of arrays) plus the
    hyperparameters that produced it."""
    family: str
    params: Any
    hyper: Dict[str, Any]
    num_classes: int = 2


class ModelFamily(abc.ABC):
    """A homogeneous model family whose hyperparameter grid can be vmapped.

    Mesh sharding contract (docs/parallel.md): when the ModelSelector sweep
    runs over a ('data', 'model') mesh, ``fit_batch`` / ``sweep_fit_batch``
    are traced into one GSPMD program whose operands carry these shardings —
    X rows over 'data' (features replicated), y over 'data', weights
    ('model', 'data'), grid arrays over 'model' — and the returned stacked
    params must keep their leading config axis partitionable over 'model'
    (no cross-config reductions; per-config math only, which every vmapped
    fit satisfies by construction). ``shardable=False`` opts a family's
    config axis out (sequential-scan fits whose chunk loop is not a single
    vmapped program); rows still shard over 'data'.
    """

    #: family name, e.g. "OpLogisticRegression"
    name: str = ""
    #: problem kinds: subset of {"binary", "multiclass", "regression"}
    supports: frozenset = frozenset()
    #: config (B) axis may shard over the mesh 'model' axis; False keeps
    #: configs whole per device (see the sharding contract above)
    shardable: bool = True
    #: grid arrays may be passed as ONE packed traced f32 device block
    #: (uploaded sharded over 'model', donated for buffer reuse) instead of
    #: host constants baked into the trace. Only safe for families whose fit
    #: reads grid values as arrays; families deriving STATIC trace structure
    #: from the grid (tree depth bucketing) must keep host constants
    traced_grid_ok: bool = False
    #: fitted-param keys where ±inf is a STRUCTURAL sentinel, not divergence
    #: (tree thresholds use +inf for "stopped node routes every row left");
    #: the refit non-finite guard (robustness/guards.params_finite) checks
    #: these keys for NaN only
    inf_ok_params: tuple = ()

    @abc.abstractmethod
    def default_grid(self, problem: str) -> List[Dict[str, Any]]:
        """Default hyperparameter grid (reference DefaultSelectorParams)."""

    @abc.abstractmethod
    def fit_batch(self, X: jnp.ndarray, y: jnp.ndarray,
                  weights: jnp.ndarray, grid: Dict[str, jnp.ndarray],
                  num_classes: int) -> Any:
        """Fit B configurations at once.

        X: (n, d); y: (n,); weights: (B, n) row weights (0 = excluded);
        grid: dict of (B,) hyperparameter arrays. Returns stacked params with
        leading axis B.
        """

    def sweep_fit_batch(self, X: jnp.ndarray, y: jnp.ndarray,
                        weights: jnp.ndarray, grid: Dict[str, jnp.ndarray],
                        num_classes: int) -> Any:
        """``fit_batch`` for CV-sweep candidates. Families may trade exact
        fitted state for sweep throughput here (tree families use
        sample-based leaf values — validation scoring only); the selector
        refits the winner through plain ``fit_batch``. Default: identical
        to ``fit_batch``."""
        return self.fit_batch(X, y, weights, grid, num_classes)

    def fit_span_attrs(self, rows: int, features: int,
                       grid: Sequence[Dict[str, Any]], num_classes: int,
                       sweep: bool) -> Dict[str, Any]:
        """What this family's own schedule fixes about one fit of ``grid``
        (one entry per lane) over a (rows, features) matrix, as attributes
        for the ``sweep.family`` and ``selector.refit`` spans: how many
        contractions a solver runs, into how many chunks a chunker splits
        the lanes. Computed from shapes by the rule the program itself
        uses; nothing is fetched. Default: none."""
        return {}

    def predict_span_attrs(self, fitted: "FittedParams",
                           rows: int) -> Dict[str, Any]:
        """What one predict of ``fitted`` over a matrix of ``rows`` rows is
        made of, as attributes for the spans that launch it
        (``evaluate.predict``, ``predict.parts``): from shapes, nothing is
        fetched. Default: none."""
        return {}

    @abc.abstractmethod
    def predict_batch(self, params: Any, X: jnp.ndarray,
                      num_classes: int) -> jnp.ndarray:
        """Scores for stacked params: (B, n) margins / (B, n, C) probabilities."""

    @abc.abstractmethod
    def predict_one(self, fitted: FittedParams, X: jnp.ndarray) -> Dict[str, np.ndarray]:
        """Single-model prediction parts: {'prediction', 'probability'?, 'rawPrediction'?}."""

    def predict_parts(self, fitted: FittedParams,
                      X: jnp.ndarray) -> Optional[Dict[str, jnp.ndarray]]:
        """jit-traceable dual of ``predict_one``: identical parts as jnp
        arrays (the fitted params close over the trace as constants), so the
        winning model's Prediction emission can compile INTO the one fused
        serve program (local/scoring.compiled_score_function — reference
        analog FitStagesUtil.scala:96-119 folds every stage in one pass).
        None = this family's predict is host-only and the serve-path fusion
        must leave the model stage outside the compiled program."""
        return None

    def feature_importances(self, fitted: "FittedParams") -> Optional[np.ndarray]:
        """Per-input-dimension contribution scores for ModelInsights
        (|coefficients| for linear families, split frequencies for trees);
        None when the family has no natural attribution."""
        p = fitted.params
        if isinstance(p, dict):
            if "coef" in p:
                return np.abs(np.asarray(p["coef"])).reshape(-1)
            if "W" in p:
                return np.abs(np.asarray(p["W"])).mean(axis=-1).reshape(-1)
            if "feat" in p or "feat_lv" in p:
                # tree ensembles (heap or slot-chain layout): how often each
                # feature splits; sentinel-binned entries are stopped/padded
                # nodes, not real splits, and must not count toward slot 0
                fk, bk = (("feat", "bins") if "feat" in p
                          else ("feat_lv", "bins_lv"))
                feats = np.asarray(p[fk]).reshape(-1).astype(np.int64)
                if bk in p and "edges" in p:
                    nb = np.asarray(p["edges"]).shape[-1] + 1
                    feats = feats[np.asarray(p[bk]).reshape(-1) < nb]
                feats = feats[feats >= 0]
                d = int(np.asarray(p.get("num_features", feats.max() + 1 if
                                         feats.size else 1)))
                counts = np.bincount(feats, minlength=d).astype(np.float64)
                return counts / max(counts.sum(), 1.0)
        return None

    def select_params(self, batched: Any, idx: int) -> Any:
        """Extract configuration ``idx`` from stacked params."""
        import jax
        return jax.tree_util.tree_map(lambda a: np.asarray(a[idx]), batched)

    #: score CV candidates on their own fold's gathered rows (capped at
    #: OpValidator.max_eval_rows) instead of full-row masked scoring; with
    #: the cap this wins even for single-matmul predicts, and the fold
    #: gather is shared across families. See OpValidator.validate.
    fold_sliced_predict: bool = True

    def slice_params(self, batched: Any, lo: int, hi: int) -> Any:
        """Slice a config-range [lo, hi) of stacked params, on device.
        Families whose params carry unbatched leaves (shared bin edges,
        static ints) override this to leave those leaves whole."""
        import jax
        return jax.tree_util.tree_map(lambda a: a[lo:hi], batched)

    def grid_to_arrays(self, grid: Sequence[Dict[str, Any]]) -> Dict[str, jnp.ndarray]:
        keys = sorted({k for g in grid for k in g})
        return {k: jnp.asarray([g[k] for g in grid], dtype=jnp.float32) for k in keys}


MODEL_REGISTRY: Dict[str, ModelFamily] = {}


def register_family(family: ModelFamily) -> ModelFamily:
    MODEL_REGISTRY[family.name] = family
    return family
