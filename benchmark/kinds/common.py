"""What the closed-loop kinds share: the end-to-end arithmetic a traffic
file asks for under ``reports``, and the comparison with the reference."""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import arith, reference, workflows
from ..datagen import Generated
from ..harness import Check


def reported(traffic: Dict[str, Any], ops: Sequence[arith.Op],
             units_per_op: float) -> Dict[str, Optional[float]]:
    """``reports`` maps an end-to-end metric's name to its arithmetic."""
    out: Dict[str, Optional[float]] = {}
    for name, how in traffic["reports"].items():
        if how == "median_op_seconds":
            out[name] = arith.median_op_seconds(
                ops, int(traffic.get("min_ops", 1)))
        elif how == "units_per_second":
            out[name] = arith.rate_over_window(units_per_op, ops)
        else:
            raise ValueError(f"unknown arithmetic {how!r} for {name}")
    return out


def reference_scores(model, built_names: Tuple[str, str, str], scored,
                     gen: Generated, precision: str = "f32"
                     ) -> Tuple[np.ndarray, np.ndarray, str]:
    """The reference's feature matrix and class-1 scores for the rows of
    ``gen``, from raw columns, the vector's slot descriptions and the
    winner's parameters; and the Prediction key they compare with."""
    vector_name, checked_name, _ = built_names
    family, params = workflows.fitted_of(model)
    X = reference.feature_matrix(
        gen.columns, gen.types, workflows.slots_of(scored[vector_name]),
        workflows.slots_of(scored[checked_name]))
    return X, reference.class1_score(family, params, X, precision), \
        reference.score_key(family)


def compare_with_reference(model, built_names, scored, gen: Generated,
                           limits: Dict[str, Any]) -> List[Check]:
    """Feature vector and class-1 score of the program against the
    reference's, and the held-out AuROC against its floor."""
    _, checked_name, pred_name = built_names
    X, ref, key = reference_scores(model, built_names, scored, gen)
    got_X = np.asarray(scored[checked_name].values, dtype=np.float32)
    got = workflows.prediction_part(scored[pred_name], key)
    checks = [
        Check("feature_vector_max_abs_diff",
              float(np.abs(got_X - X).max()) if got_X.shape == X.shape
              else float("nan"),
              limits.get("feature_vector_max_abs_diff")),
        Check("score_max_abs_diff",
              float(np.abs(got - ref).max()),
              limits.get("score_max_abs_diff")),
        Check("score_mean_abs_diff",
              float(np.abs(got - ref).mean()), None),
        Check("scores_finite",
              float(np.isfinite(got).all()), 1.0, "min"),
        Check("auroc",
              reference.auroc(got, gen.label), limits.get("auroc_floor"),
              "min")]
    if gen.true_prob is not None:
        checks.append(Check("auroc_of_label_rule",
                            reference.auroc(gen.true_prob, gen.label), None))
    return checks


TRAINING_LIMITS = ("refit_coef_max_abs_diff", "refit_score_max_abs_diff",
                   "cv_metric_abs_diff")
_LR = "OpLogisticRegression"


def _lr_point(report: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """(regParam, reported mean validation metric) of the sweep's best
    logistic grid point without an L1 term, the one family and penalty the
    reference can train to its optimum."""
    points = [(m, g["regParam"]) for g, m in zip(
        report["grids"].get(_LR, []), report["by_family"].get(_LR, []))
        if not g.get("elasticNetParam")]
    if not points:
        return None
    metric, reg = max(points)
    return float(reg), float(metric)


def compare_training(loop, precision: str = "f64") -> List[Check]:
    """What the timed train fitted against the reference's own training of
    it: the logistic regression of the sweep's best L2 point, fitted in
    float64 to its optimum on the rows the stock selector fits on, from the
    reference's feature matrix of the raw training rows.

    * ``cv_metric_abs_diff``: the sweep's reported mean validation AuPR of
      that point against the reference's k-fold AuPR (whoever wins);
    * ``refit_coef_max_abs_diff`` (in standardised units) and
      ``refit_score_max_abs_diff`` (held-out probability): the winner's
      refit against the reference's fit, where that point is the winner.

    With ``precision="bf16"`` the control stands in the program's place:
    the reference's fit with bfloat16 features and temporaries."""
    model, names, held, hold_gen, limits = loop.compared
    if not any(k in limits for k in TRAINING_LIMITS):
        return []
    report, cfg = loop.reports[-1], loop.config
    sel = cfg["workflow"]["selector"]
    point = _lr_point(report)
    if point is None:
        loop.ctx.log("no L2 logistic point in the sweep: training is not "
                     "compared")
        return []
    reg, reported = point
    if getattr(loop, "reference_fit", (None,))[0] != reg:
        gen = loop.train_gen
        rows, _ = reference.reserved_split(
            gen.rows, sel.get("reserve_test_fraction", 0.1),
            sel.get("seed", 42))
        slots = (workflows.slots_of(held[names[0]]),
                 workflows.slots_of(held[names[1]]))
        X = reference.feature_matrix(
            {k: v[rows] for k, v in gen.columns.items()}, gen.types, *slots)
        y = gen.label[rows]
        X_hold = reference.feature_matrix(hold_gen.columns, hold_gen.types,
                                          *slots)
        fit = reference.fit_logistic(X, y, reg)
        loop.reference_fit = (reg, X, y, X_hold, fit)
        loop.reference_cv = None
        loop.ctx.log(f"reference: logistic regParam {reg} on {len(rows)} "
                     f"rows, {fit['iterations']} Newton steps")
    _, X, y, X_hold, fit = loop.reference_fit
    start = (fit["coef"], fit["bias"])
    checks: List[Check] = []
    family, params = workflows.fitted_of(model)
    hyper = loop.reports[-1]["hyper_dict"]
    is_point = (family == _LR and not hyper.get("elasticNetParam")
                and abs(float(hyper["regParam"]) - reg) <= 1e-6 * reg)
    if precision != "f64":
        low = reference.fit_logistic(X, y, reg, precision, max_iter=10)
        got_coef, got_prob = low["coef"], reference.logistic_prob(X_hold, low)
    elif is_point:
        got_coef = np.asarray(params["coef"], dtype=np.float64)
        got_prob = workflows.prediction_part(held[names[2]],
                                             reference.score_key(family))
    else:
        loop.ctx.log(f"winner {family} {hyper}: its refit has no plain "
                     f"form here; the sweep's logistic point is compared")
        got_coef = None
    if got_coef is not None:
        checks += [
            Check("refit_coef_max_abs_diff",
                  float(np.abs((got_coef - fit["coef"]) * fit["std"]).max()),
                  limits.get("refit_coef_max_abs_diff")),
            Check("refit_score_max_abs_diff",
                  float(np.abs(got_prob - reference.logistic_prob(
                      X_hold, fit)).max()),
                  limits.get("refit_score_max_abs_diff"))]
    if (sel["validation"] == "cross_validation"
            and report["metric_name"] == "AuPR"):
        if precision == "f64":
            if loop.reference_cv is None:
                loop.reference_cv = reference.cv_aupr(
                    X, y, reg, int(sel.get("folds", 3)), loop.ctx.seed, start)
            got_cv = reported
        else:
            got_cv = reference.cv_aupr(X, y, reg, int(sel.get("folds", 3)),
                                       loop.ctx.seed, start, precision)
        checks.append(Check("cv_metric_abs_diff",
                            abs(got_cv - loop.reference_cv),
                            limits.get("cv_metric_abs_diff")))
        loop.ctx.log(f"validation AuPR of logistic regParam {reg}: "
                     f"{got_cv!r} against the reference's "
                     f"{loop.reference_cv!r}")
    return checks


def control_checks(loop) -> List[Check]:
    """The same numbers with the control in the program's place: the
    reference computed in bfloat16 (features, thresholds, coefficients;
    for the training numbers a bfloat16 fit), compared with the float32
    or float64 reference. A sound limit fails it."""
    model, names, scored, gen, limits = loop.compared
    X, ref, _ = reference_scores(model, names, scored, gen)
    _, low, _ = reference_scores(model, names, scored, gen, "bf16")
    training = (compare_training(loop, "bf16")
                if hasattr(loop, "reference_fit") else [])
    return training + [
        Check("feature_vector_max_abs_diff",
              float(np.abs(reference.to_bf16(X) - X).max()),
              limits.get("feature_vector_max_abs_diff")),
        Check("score_max_abs_diff", float(np.abs(low - ref).max()),
              limits.get("score_max_abs_diff")),
        Check("score_mean_abs_diff", float(np.abs(low - ref).mean()), None),
        Check("auroc", reference.auroc(low, gen.label),
              limits.get("auroc_floor"), "min")]
