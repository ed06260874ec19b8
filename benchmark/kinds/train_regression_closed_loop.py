"""One caller trains a regression selector on the same in-memory table again
and again. The loop is ``train_closed_loop``'s; what differs is where the
rows come from (``datagen_regression``: a timestamp and an integer column
beside categoricals and reals, a positive heavy-tailed label), the sweep's
report (the generalised-linear grid names its families by word) and what
``correct`` compares (``reference_regression``): the winner's prediction,
the winning linear or generalised-linear point's refit against its float64
optimum, the sweep's RMSE of that point, and that every generalised-linear
lane fitted something."""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import datagen_regression, workflows
from .. import reference_regression as ref
from .. import roofline_bytes  # noqa: F401  (registers span_bytes_roofline)
from ..harness import Check
from . import train_closed_loop

TRAINING_LIMITS = ("refit_coef_max_abs_diff", "refit_pred_max_rel_diff",
                   "cv_metric_rel_diff")
#: the families whose every grid point the reference fits to its optimum
PLAIN = (ref.LINEAR, ref.GLM)


def slots_of(column) -> List[ref.Slot]:
    """(parent feature, indicator value, descriptor value) per slot of a
    vector column, as plain tuples for the reference."""
    vm = column.metadata["vector_meta"]
    return [(c.parent_feature_name, c.indicator_value, c.descriptor_value)
            for c in vm.columns]


def sweep_report(model, selector) -> Dict[str, Any]:
    """``workflows.sweep_report`` for a grid whose values need not be
    numbers (``family: gaussian``)."""
    s = workflows.selected_model(model).summary
    folds = getattr(selector.validator, "num_folds", 1)
    by_family = {r.family: [float(m) for m in
                            np.asarray(r.mean_metrics).reshape(-1)]
                 for r in s.validation_results}
    metrics = [m for ms in by_family.values() for m in ms]
    return {"family": s.best_model_type,
            "hyper": json.dumps(dict(s.best_hyper), sort_keys=True,
                                default=float),
            "hyper_dict": dict(s.best_hyper),
            "metric": float(s.best_metric_value),
            "fits": int(folds * len(metrics)),
            "finite": bool(np.all(np.isfinite(metrics))) and bool(metrics),
            "quarantined": len(s.quarantined),
            "metric_name": s.validation_metric,
            "grids": {r.family: [dict(g) for g in r.grid]
                      for r in s.validation_results},
            "by_family": by_family}


def _diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.abs(a - b).max()) if a.shape == b.shape else float("nan")


def _same_point(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    return json.dumps(a, sort_keys=True, default=float) == json.dumps(
        b, sort_keys=True, default=float)


class Loop(train_closed_loop.Loop):
    def __init__(self, ctx):
        super().__init__(ctx)
        # The configuration states linear fits from moments of the one
        # shared matrix. A program that copies the table for every lane (the
        # parent of PR 30: 8 GB a temporary at this size) cannot run the
        # cell: it stops here, before any row is made.
        from transmogrifai_tpu.models import linear
        if not hasattr(linear, "gram_block_rows"):
            raise SystemExit(
                "train_regression_closed_loop: this program's linear fits "
                "hold a copy of the table for every lane (models/linear.py "
                "before PR 30); the cell cannot run on it")
        self._forget_reference()

    def _forget_reference(self) -> None:
        """Drop what the reference kept of the compared model: its matrices
        of the selector's and the held-out rows, its optima, its k-fold
        numbers."""
        self._fit_rows: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._hold_X: Optional[np.ndarray] = None
        self._optima: Dict[str, Dict[str, Any]] = {}
        self._cv: Dict[str, float] = {}

    # -- set-up: as the binary loop's, the rows from the fare rule ----------
    def setup(self) -> None:
        cfg = self.config
        rows = int(self.traffic.get("rows") or cfg["rows"])
        gen = datagen_regression.generate(cfg, self.ctx.seed,
                                          rows + int(cfg["holdout_rows"]))
        self.train_gen = gen.slice(0, rows)
        self.holdout_gen = gen.slice(rows, gen.rows)
        self.table = workflows.table_of(self.train_gen, cfg["label"])
        self.units_per_op = float(rows)
        self.prepare_op()
        self.op()
        self.warm_report = self.reports.pop()

    def op(self) -> None:
        model = self.built.workflow.train()
        workflows.wait_for_model(model)
        self.model = model
        self.reports.append(sweep_report(model, self.built.selector))

    # -- the reference's view of the last model ------------------------------
    def _matrix(self, gen, rows: Optional[np.ndarray] = None) -> np.ndarray:
        _, names, held, _, _ = self.compared
        cols = gen.columns if rows is None else {
            k: v[rows] for k, v in gen.columns.items()}
        return ref.feature_matrix(cols, gen.types, slots_of(held[names[0]]),
                                  slots_of(held[names[1]]))

    def _holdout_matrix(self) -> np.ndarray:
        """The reference's matrix of the held-out rows (kept, as the
        selector's rows are)."""
        if self._hold_X is None:
            self._hold_X = self._matrix(self.compared[3])
        return self._hold_X

    def _selector_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """The reference's matrix and label of the rows the selector fits
        on (kept: every comparison and control reads the same)."""
        if self._fit_rows is None:
            gen, sel = self.train_gen, self.config["workflow"]["selector"]
            rows, _ = ref.reserved_split(
                gen.rows, sel.get("reserve_test_fraction", 0.1),
                sel.get("seed", 42))
            self._fit_rows = (self._matrix(gen, rows),
                              gen.label[rows].astype(np.float64))
        return self._fit_rows

    def _optimum(self, family: str, hyper: Dict[str, Any]) -> Dict[str, Any]:
        X, y = self._selector_rows()
        key = json.dumps([family, hyper], sort_keys=True, default=float)
        if key not in self._optima:
            self._optima[key] = ref.fit_point(family, hyper, X, y)
            self.ctx.log(f"reference: {family} {hyper} on {len(y)} rows, "
                         f"{self._optima[key]['iterations']} iterations")
        return self._optima[key]

    def _best_point(self, families, wanted=lambda g: True
                    ) -> Optional[Tuple[str, Dict[str, Any], float]]:
        """(family, grid point, reported mean RMSE) of the sweep's best
        point among ``families`` that ``wanted`` admits."""
        report = self.reports[-1]
        points = [(m, fam, g) for fam in families
                  for g, m in zip(report["grids"].get(fam, []),
                                  report["by_family"].get(fam, []))
                  if wanted(g)]
        if not points:
            return None
        m, fam, g = min(points, key=lambda p: p[0])
        return fam, g, float(m)

    # -- the comparisons -----------------------------------------------------
    def compare_scores(self, precision: str = "f32") -> List[Check]:
        """Feature vector, prediction and held-out R2 of the program against
        the reference's (``bf16``: the control in the program's place,
        against the reference itself)."""
        model, names, held, gen, limits = self.compared
        family, params = workflows.fitted_of(model)
        X = self._holdout_matrix()
        y = gen.label.astype(np.float64)
        want = ref.predict(family, params, X)
        if precision == "f32":
            got_X = np.asarray(held[names[1]].values, dtype=np.float32)
            got = workflows.prediction_part(held[names[2]],
                                            "prediction").astype(np.float64)
        else:
            got_X = ref.to_bf16(X)
            got = ref.predict(family, params, X, precision)
        return [
            Check("feature_vector_max_abs_diff", _diff(got_X, X),
                  limits.get("feature_vector_max_abs_diff")),
            Check("pred_max_rel_diff", _diff(got, want) / float(y.std()),
                  limits.get("pred_max_rel_diff")),
            Check("pred_mean_rel_diff", float(np.abs(got - want).mean()
                                              / y.std())
                  if got.shape == want.shape else float("nan"), None),
            Check("preds_finite", float(np.isfinite(got).all()), 1.0, "min"),
            Check("r2", ref.r2(got, y), limits.get("r2_floor"), "min"),
            Check("rmse_over_label_std", ref.rmse(got, y) / float(y.std()),
                  None)]

    def _refit_checks(self, family, optimum, fit) -> List[Check]:
        """A fit's coefficients (standardised units) and held-out
        predictions against the reference's optimum of the same point."""
        _, _, _, gen, limits = self.compared
        X_hold = self._holdout_matrix()
        scale = float(gen.label.astype(np.float64).std())
        return [
            Check("refit_coef_max_abs_diff",
                  _diff(np.asarray(fit["coef"], dtype=np.float64)
                        * optimum["std"], optimum["coef"] * optimum["std"]),
                  limits.get("refit_coef_max_abs_diff")),
            Check("refit_intercept_abs_diff",
                  abs(float(fit["bias"]) - float(optimum["bias"])), None),
            Check("refit_pred_max_rel_diff",
                  _diff(ref.predict(family, fit, X_hold),
                        ref.predict(family, optimum, X_hold)) / scale,
                  limits.get("refit_pred_max_rel_diff"))]

    def compare_training(self, control: Optional[str] = None) -> List[Check]:
        """What the timed train fitted against the reference's own training
        of it: the sweep's best linear or generalised-linear point, in
        float64 at its optimum on the rows the stock selector fits on.

        * ``refit_coef_max_abs_diff``, ``refit_pred_max_rel_diff``: the
          winner's refit against that optimum, where that point is the
          winner (said in the output where it is not);
        * ``cv_metric_rel_diff``: the sweep's reported mean RMSE of that
          point against the reference's k-fold RMSE (whoever wins);
        * ``poisson_lanes_fitted``: the poisson points of the sweep whose
          RMSE is under the label's standard deviation on those rows (a
          fit that predicts a constant is not).

        ``control``: ``"bf16"`` (the reference's fit of that point with
        bfloat16 features), ``"ista"`` (the sweep's best point with an L1
        term by 60 ISTA steps at ``1 / trace``) or ``"irls0"`` (the poisson
        points by IRLS from ``theta = 0``): the program's schedules before
        PR 30, each in the program's place."""
        model, names, held, _, limits = self.compared
        if not any(k in limits for k in TRAINING_LIMITS):
            return []
        report = self.reports[-1]
        sel = self.config["workflow"]["selector"]
        X, y = self._selector_rows()
        if control == "irls0":
            return [self._poisson_check(
                [ref.rmse(ref.predict(ref.GLM, ref.irls_from_zero(
                    X, y, g["regParam"], ref.POISSON), X), y)
                 for g in self._poisson_grid()])]
        point = self._best_point(
            (ref.LINEAR,) if control == "ista" else PLAIN,
            (lambda g: g.get("elasticNetParam", 0) > 0)
            if control == "ista" else (lambda g: True))
        if point is None:
            self.ctx.log("no linear point in the sweep: training is not "
                         "compared")
            return []
        family, hyper, reported = point
        optimum = self._optimum(family, hyper)
        if control == "bf16":
            return self._refit_checks(family, optimum, ref.fit_point(
                family, hyper, X, y, "bf16"))
        if control == "ista":
            return self._refit_checks(family, optimum, ref.ista_linear(
                X, y, hyper["regParam"], hyper["elasticNetParam"]))
        checks: List[Check] = []
        if report["family"] == family and _same_point(report["hyper_dict"],
                                                      hyper):
            _, params = workflows.fitted_of(model)
            checks += self._refit_checks(family, optimum, params)
        else:
            self.ctx.log(f"winner {report['family']} {report['hyper']}: its "
                         f"refit has no plain form here; the sweep's best "
                         f"linear point is compared")
        if (sel["validation"] == "cross_validation"
                and report["metric_name"] == "RootMeanSquaredError"):
            key = json.dumps([family, hyper], sort_keys=True, default=float)
            if key not in self._cv:
                self._cv[key] = ref.cv_rmse(X, y, family, hyper,
                                            int(sel.get("folds", 3)),
                                            self.ctx.seed)
            checks.append(Check("cv_metric_rel_diff",
                                abs(reported - self._cv[key])
                                / self._cv[key],
                                limits.get("cv_metric_rel_diff")))
            self.ctx.log(f"validation RMSE of {family} {hyper}: "
                         f"{reported!r} against the reference's "
                         f"{self._cv[key]!r}")
        glm = list(zip(report["grids"].get(ref.GLM, []),
                       report["by_family"].get(ref.GLM, [])))
        self.ctx.log("validation RMSE of the generalised-linear points: "
                     + ", ".join(f"{g.get('family')} {g['regParam']}: {m:.4f}"
                                 for g, m in glm)
                     + f"; the label's deviation {float(y.std()):.4f}")
        checks.append(self._poisson_check(
            [m for g, m in glm if g.get("family") == "poisson"]))
        return checks

    def _poisson_grid(self) -> List[Dict[str, Any]]:
        return [g for g in self.reports[-1]["grids"].get(ref.GLM, [])
                if g.get("family") == "poisson"]

    def _poisson_check(self, rmses: List[float]) -> Check:
        _, y = self._selector_rows()
        return Check("poisson_lanes_fitted",
                     float(sum(m < float(y.std()) for m in rmses)),
                     float(len(self._poisson_grid())), "min")

    def controls(self) -> Dict[str, List[Check]]:
        """The checks with each control in the program's place, by the
        control's name; a sound limit fails the numbers meant for it."""
        return {
            "bf16 reference": (self.compare_training("bf16")
                               + self.compare_scores("bf16")),
            "ista 60 x 1/trace": self.compare_training("ista"),
            "irls from theta 0": self.compare_training("irls0")}

    def check(self) -> List[Check]:
        cfg, limits = self.config, self.config["check"]
        last = self.reports[-1]
        self.ctx.log(
            f"winner {last['family']} {last['hyper']} metric "
            f"{last['metric']!r}; winners of the window: "
            f"{sorted({(r['family'], r['hyper']) for r in self.reports})}")
        checks = [
            Check("fits", float(min(r["fits"] for r in self.reports)),
                  float(cfg["workflow"]["expected_fits"]), "min"),
            Check("fits_finite",
                  float(all(r["finite"] for r in self.reports)), 1.0, "min"),
            Check("quarantined_fits",
                  float(sum(r["quarantined"] for r in self.reports)), 0.0),
            Check("model_fault_sections",
                  float(len(workflows.model_faults(self.model))), 0.0)]
        held_table = self._score_holdout()
        self._forget_reference()
        names = self.compared[1]
        self.ctx.log("feature vector: %d of %d derived columns kept" % tuple(
            len(slots_of(self.compared[2][n])) for n in (names[1], names[0])))
        checks += self.compare_scores()
        checks += self.compare_training()
        n = min(int(limits.get("parity_rows", 10000)), self.holdout_gen.rows)
        part = held_table.take(np.arange(n))
        planned = np.asarray(self.model.score(table=part)[names[2]].values)
        eager = np.asarray(workflows.score_eager(self.model,
                                                 part)[names[2]].values)
        checks.append(Check("planned_vs_eager_max_abs_diff",
                            _diff(planned, eager),
                            limits.get("planned_vs_eager_max_abs_diff")))
        checks.append(Check("fault_kinds_counted",
                            float(len(workflows.fault_counts())), 0.0))
        return checks
