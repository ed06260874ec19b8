"""The only place the benchmark touches the program: its public entry
points (``FeatureBuilder``, ``transmogrify``, ``sanity_check``, the
selector factories, ``OpWorkflow.train``, ``OpWorkflowModel.score``) and
its switches for spans, metrics and planning.

Everything is built from a configuration's ``columns`` and ``workflow``
sections; nothing here knows a configuration by name.
"""
from __future__ import annotations

import contextlib
import json
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .datagen import Generated

#: FaultLog kinds and summary sections that must be empty in a sound run
#: (chip_smoke.py's list)
FAULT_KINDS = ("quarantine", "retry", "plan_fallback", "oom_downshift",
               "breaker_degraded", "fatal", "aot_fallback")
SUMMARY_FAULT_KEYS = ("quarantined", "retries", "planFallbacks",
                      "oomDownshifts", "breakerDegraded", "fatal")


def table_of(gen: Generated, label_name: str):
    """A ``FeatureTable`` over generated rows (no nulls: every slot valid)."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.table import Column, FeatureTable

    n = gen.rows
    valid = np.ones(n, dtype=bool)
    cols = {name: Column(getattr(T, gen.types[name]), arr, valid)
            for name, arr in gen.columns.items()}
    cols[label_name] = Column(T.RealNN, gen.label, valid)
    return FeatureTable(cols, n)


def build_selector(problem: str, spec: Dict[str, Any]):
    from transmogrifai_tpu.impl.selector import factories

    factory = {"binary": factories.BinaryClassificationModelSelector,
               "multiclass": factories.MultiClassificationModelSelector,
               "regression": factories.RegressionModelSelector}[problem]
    models = (None if spec["models"] == "stock"
              else [(fam, list(grid)) for fam, grid in spec["models"]])
    if spec["validation"] == "cross_validation":
        return factory.with_cross_validation(
            num_folds=int(spec.get("folds", 3)), models=models)
    if spec["validation"] == "train_validation_split":
        return factory.with_train_validation_split(models=models)
    raise ValueError(f"unknown validation {spec['validation']!r}")


@dataclass
class Built:
    workflow: Any
    prediction: Any      # result feature
    vector: Any          # combined feature vector (before the checker)
    checked: Any         # the vector the model reads
    selector: Any


def build_workflow(config: Dict[str, Any], table) -> Built:
    """The configuration's workflow over ``table``: raw features ->
    ``transmogrify`` -> ``sanity_check`` -> selector."""
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.workflow import OpWorkflow

    wspec = config["workflow"]
    if list(wspec["prepare"]) != ["transmogrify", "sanity_check"]:
        raise ValueError(f"unknown prepare steps {wspec['prepare']!r}")
    label = FeatureBuilder.RealNN(config["label"]).extract_field() \
        .as_response()
    feats = [getattr(FeatureBuilder, c["type"])(c["name"]).extract_field()
             .as_predictor() for c in config["columns"]]
    vector = tg.transmogrify(feats)
    checked = vector.sanity_check(label)
    selector = build_selector(config["problem"], wspec["selector"])
    pred = selector.set_input(label, checked).get_output()
    wf = OpWorkflow().set_input_table(table).set_result_features(pred)
    return Built(wf, pred, vector, checked, selector)


def wait_for_model(model) -> None:
    """Block until the fitted winner's parameters are on the device: the
    end of a timed train."""
    import jax
    jax.block_until_ready(selected_model(model).fitted.params)


def selected_model(model):
    from transmogrifai_tpu.impl.selector.model_selector import SelectedModel
    return next(s for s in model.stages if isinstance(s, SelectedModel))


def fitted_of(model) -> Tuple[str, Dict[str, np.ndarray]]:
    """The winner's family name and parameters as plain numpy arrays."""
    f = selected_model(model).fitted
    return f.family, {k: np.asarray(v) for k, v in f.params.items()}


def sweep_report(model, selector) -> Dict[str, Any]:
    """Winner, number of fits and whether every fit's metric is finite."""
    s = selected_model(model).summary
    folds = getattr(selector.validator, "num_folds", 1)
    metrics = [float(m) for r in s.validation_results
               for m in np.asarray(r.mean_metrics).reshape(-1)]
    return {"family": s.best_model_type,
            "hyper": json.dumps(dict(s.best_hyper), sort_keys=True,
                                default=float),
            "hyper_dict": dict(s.best_hyper),
            "metric": float(s.best_metric_value),
            "fits": int(folds * sum(len(r.grid)
                                    for r in s.validation_results)),
            "finite": bool(np.all(np.isfinite(metrics))) and bool(metrics),
            "quarantined": len(s.quarantined),
            "metric_name": s.validation_metric,
            "grids": {r.family: [{k: float(v) for k, v in g.items()}
                                 for g in r.grid]
                      for r in s.validation_results},
            "by_family": {r.family: [float(m) for m in
                                     np.asarray(r.mean_metrics).reshape(-1)]
                          for r in s.validation_results}}


@contextlib.contextmanager
def refit_through_sweep_path():
    """The program's own lower-precision path in the refit's place: while
    this is open, a winner refits through its family's sweep fit (bfloat16
    per-row temporaries and a shorter schedule, where the family has one)
    instead of the full-precision fit. A control, never a timed run."""
    from transmogrifai_tpu.models.api import MODEL_REGISTRY, ModelFamily
    patched = []
    for cls in {type(f) for f in MODEL_REGISTRY.values()}:
        if cls.sweep_fit_batch is not ModelFamily.sweep_fit_batch:
            patched.append((cls, cls.__dict__.get("fit_batch")))
            cls.fit_batch = cls.sweep_fit_batch
    try:
        yield
    finally:
        for cls, own in patched:
            if own is None:
                del cls.fit_batch
            else:
                cls.fit_batch = own


def slots_of(column) -> List[Tuple[str, Optional[str]]]:
    """(parent feature, indicator value) per slot of a vector column, as
    plain tuples for the reference."""
    vm = column.metadata["vector_meta"]
    return [(c.parent_feature_name, c.indicator_value) for c in vm.columns]


def prediction_part(column, key: str) -> np.ndarray:
    keys = list(column.metadata["keys"])
    return np.asarray(column.values)[:, keys.index(key)]


def fault_counts() -> Dict[str, float]:
    """Process-wide ``tg_faults_total`` by kind, for the kinds that must be
    zero."""
    from transmogrifai_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.registry().snapshot().get("tg_faults_total", {})
    out = {k.split("=", 1)[-1]: float(v) for k, v in snap.items()}
    return {k: v for k, v in out.items() if k in FAULT_KINDS and v}


def model_faults(model) -> Dict[str, Any]:
    """The non-empty fault sections of a trained model's summary and fault
    log."""
    faults = model.summary()["faults"]
    dirty = {k: len(faults[k]) if hasattr(faults[k], "__len__")
             else faults[k] for k in SUMMARY_FAULT_KEYS if faults[k]}
    log = getattr(model, "_fault_log", None)
    if log is not None:
        bad = [r.kind for r in log.reports if r.kind in FAULT_KINDS]
        if bad:
            dirty["faultLog"] = bad[:5]
    return dirty


def enable_metrics() -> None:
    from transmogrifai_tpu.observability import metrics as obs_metrics
    obs_metrics.enable_metrics(True)


def enable_spans(on: bool) -> None:
    from transmogrifai_tpu.observability import trace as obs_trace
    obs_trace.enable_tracing(bool(on))
    if on:
        obs_trace.tracer().clear()


def finished_spans() -> Tuple[List[Any], int]:
    """The program's finished spans and its tracer's epoch
    (``perf_counter_ns``)."""
    from transmogrifai_tpu.observability import trace as obs_trace
    t = obs_trace.tracer()
    return t.finished(), int(t.epoch_ns)


def score_eager(model, table):
    """``model.score`` with the transform plan switched off."""
    from transmogrifai_tpu import plan as plan_mod
    plan_mod.enable_planning(False)
    try:
        return model.score(table=table)
    finally:
        plan_mod.enable_planning(None)
