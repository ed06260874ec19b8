"""DAG scheduler: stage layering, layer-wise fit and transform.

Mirrors the reference scheduler (reference:
core/src/main/scala/com/salesforce/op/utils/stages/FitStagesUtil.scala):
``compute_dag`` groups stages into layers by max distance-to-result
(computeDAG:173-198); ``fit_and_transform_dag`` folds over layers fitting
estimators then applying transformers (fitAndTransformDAG:213-240).

Execution differences, by design: where the reference fuses all row lambdas of
a layer into a single RDD map (applyOpTransformations:96-119) and persists
every K Spark stages to sidestep Catalyst (applySparkTransformations:134-165),
here each transformer produces whole columns via jitted kernels — and the
transform-plan compiler (``plan.py``) goes one step further, tracing each
layer run's device-fusable stages into ONE jitted program so XLA fuses
*across* stage boundaries instead of dispatching N separate executables.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from .features import Feature
from .observability.trace import span as _obs_span
from .stages.base import Estimator, FeatureGeneratorStage, Transformer
from .table import FeatureTable

#: a DAG is a list of layers; each layer is a list of (stage, distance)
StageLayer = List[Tuple[Any, int]]


def compute_dag(result_features: Sequence[Feature]) -> List[StageLayer]:
    """Group all non-generator ancestor stages into layers by max distance to
    any result feature, farthest first (reference FitStagesUtil.computeDAG)."""
    dist: Dict[str, int] = {}
    stages: Dict[str, Any] = {}
    for f in result_features:
        for stage, d in f.parent_stages().items():
            if isinstance(stage, FeatureGeneratorStage):
                continue
            if stage.uid not in dist or d > dist[stage.uid]:
                dist[stage.uid] = d
                stages[stage.uid] = stage
    by_layer: Dict[int, StageLayer] = {}
    for uid, d in dist.items():
        by_layer.setdefault(d, []).append((stages[uid], d))
    return [sorted(by_layer[d], key=lambda sd: sd[0].uid)
            for d in sorted(by_layer, reverse=True)]


def validate_dag(result_features: Sequence[Feature]) -> None:
    """DAG sanity checks (reference OpWorkflow.validateStages:316): distinct
    stage uids, every feature produced by exactly one stage."""
    seen_stage: Dict[str, Any] = {}
    for f in result_features:
        for feat in f.all_features():
            st = feat.origin_stage
            if st is None:
                raise ValueError(f"feature '{feat.name}' has no origin stage")
            prev = seen_stage.get(st.uid)
            if prev is not None and prev is not st:
                raise ValueError(
                    f"duplicate stage uid '{st.uid}' for distinct stage instances")
            seen_stage[st.uid] = st


def fit_and_transform_dag(table: FeatureTable, layers: List[StageLayer],
                          checkpoint: Optional[Any] = None,
                          preloaded: Optional[Dict[str, Any]] = None,
                          retry_policy: Optional[Any] = None,
                          ) -> Tuple[FeatureTable, Dict[str, Any]]:
    """Fit estimators layer-by-layer, transforming as we go (reference
    FitStagesUtil.fitAndTransformDAG / fitAndTransformLayer).

    ``checkpoint(model)`` is invoked after each estimator fit and
    ``preloaded`` {uid → fitted model} skips refitting — together they give
    crash-resumable training (the analog of the reference's persist-every-K
    resilience, OpWorkflowModel.scala:449-455).

    ``retry_policy`` (a ``robustness.RetryPolicy``, wired by
    ``OpWorkflow.with_fault_policy``) re-runs a stage fit that fails with a
    TRANSIENT error — a failed device transfer, a reset connection — the
    analog of the reference's ``spark.task.maxFailures``. Fatal errors
    (shape/trace bugs) are never retried: the fit is deterministic, so
    re-running the same program on the same inputs cannot change them.

    Returns (transformed table, {estimator uid → fitted model}).
    """
    from .robustness import faults
    from .robustness.policy import FaultLog, FaultReport
    pre = preloaded or {}
    fitted: Dict[str, Any] = {}
    for li, layer in enumerate(layers):
        models: List[Transformer] = []
        for stage, _ in layer:
            if isinstance(stage, Estimator):
                if stage.uid in pre:
                    model = pre[stage.uid]
                    # re-wire onto this DAG's features (uids match)
                    model.input_features = stage.input_features
                    model._output_feature = stage.get_output()
                    # resume accounting: this stage's fit was skipped in
                    # favor of verified checkpoint state —
                    # summary()["resume"] reports restored vs refit
                    FaultLog.record(FaultReport(
                        site="dag.stage_fit", kind="restored",
                        detail={"uid": stage.uid,
                                "stage": type(stage).__name__}))
                else:
                    def _fit(stage=stage, li=li):
                        # deterministic preemption point: the process dies
                        # mid-DAG with earlier stages already checkpointed
                        faults.inject("preempt.stage_fit", key=stage.uid)
                        faults.inject("dag.stage_fit", key=stage.uid)
                        with _obs_span("stage.fit", cat="train", hbm=True,
                                       uid=stage.uid,
                                       stage=type(stage).__name__,
                                       layer=li):
                            return stage.fit(table)
                    if retry_policy is not None:
                        model = retry_policy.execute(
                            _fit, site=f"dag.stage_fit[{stage.uid}]")
                    else:
                        model = _fit()
                    if checkpoint is not None:
                        checkpoint(model)
                fitted[stage.uid] = model
                models.append(model)
            elif isinstance(stage, Transformer):
                models.append(stage)
            else:
                raise TypeError(f"unexpected stage kind {type(stage).__name__}")
        table = _transform_stages(table, models, cat="train", layer=li,
                                  retry_policy=retry_policy)
    return table, fitted


def _transform_stages(table: FeatureTable, models: Sequence[Any], *,
                      cat: str, layer: int = -1,
                      retry_policy: Optional[Any] = None) -> FeatureTable:
    """Run a topologically-ordered transformer sequence: as a compiled plan
    (one XLA program per device-fusable segment, ``plan.apply_planned``)
    when eligible, else eagerly stage by stage.

    Eager runs whenever per-stage semantics matter: a retry policy wants
    per-stage fault isolation (PR 1), or chaos is active
    (``plan.planning_applicable``). A profiler is no reason: it reads the
    spans of whichever path ran (``utils/profiler.py``). A planned
    run that raises falls back to eager for the run — recorded, never
    silent — so results are identical either way."""
    from . import plan as _plan
    if retry_policy is None and len(models) > 1:
        # ≥2 fusable stages: a lone-stage run gains nothing over eager
        # dispatch but would still pay the plan's probe/compile cost
        out = _plan.apply_planned(models, table, keep_intermediates=True,
                                  cat=cat, min_device_stages=2)
        if out is not None:
            return out
    for model in models:
        _plan.count_eager_dispatch(model)
        with _obs_span("stage.transform", cat=cat, hbm=True,
                       uid=getattr(model, "uid", "?"),
                       stage=type(model).__name__, layer=layer):
            table = model.transform(table)
    return table


def apply_transformations_dag(table: FeatureTable, layers: List[StageLayer]
                              ) -> FeatureTable:
    """Score-time pass: all stages must already be transformers (reference
    OpWorkflowCore.applyTransformationsDAG:321-345). The flattened
    farthest-first layer order is topological, so the whole pass plans as
    one sequence — bigger fusable segments than the per-layer train runs."""
    for layer in layers:
        for stage, _ in layer:
            if isinstance(stage, Estimator):
                raise ValueError(
                    f"stage {stage.uid} is an unfitted estimator; "
                    "score requires a fitted workflow model")
    flat = [stage for layer in layers for stage, _ in layer]
    return _transform_stages(table, flat, cat="score")
