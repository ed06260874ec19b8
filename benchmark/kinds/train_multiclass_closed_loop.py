"""One caller trains a multiclass selector on the same in-memory table again
and again. The loop is ``train_closed_loop``'s; what differs is where the
rows come from (``datagen_multiclass``) and what ``correct`` compares: all C
probabilities of the winner, the softmax refit against its float64 optimum,
the sweep's weighted F1 and the classes the selector kept
(``reference_multiclass``)."""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .. import datagen_multiclass, workflows
from .. import reference_multiclass as ref
from .. import roofline  # noqa: F401  (registers span_flops_roofline)
from ..harness import Check
from . import common, train_closed_loop

TRAINING_LIMITS = ("refit_coef_max_abs_diff", "refit_prob_max_abs_diff",
                   "cv_metric_abs_diff")


def class_probabilities(column, num_classes: int) -> np.ndarray:
    """(n, C) ``probability_i`` parts of a Prediction column."""
    keys = list(column.metadata["keys"])
    return np.asarray(column.values)[:, [keys.index(f"probability_{i}")
                                        for i in range(num_classes)]]


def _diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.abs(a - b).max()) if a.shape == b.shape else float("nan")


class Loop(train_closed_loop.Loop):
    def __init__(self, ctx):
        super().__init__(ctx)
        # The configuration states a softmax fit that reaches its optimum.
        # A program without that solver (the parent of PR 26: 200 Adam
        # steps) cannot run the cell: it stops here, before any row is made.
        from transmogrifai_tpu.models import linear
        if not hasattr(linear, "softmax_contractions"):
            raise SystemExit(
                "train_multiclass_closed_loop: this program has no softmax "
                "solver with a stated schedule (models/linear.py, PR 26); "
                "the cell cannot run on it")

    # -- set-up: as the binary loop's, the rows from the class-count rule ----
    def setup(self) -> None:
        cfg = self.config
        rows = int(self.traffic.get("rows") or cfg["rows"])
        gen = datagen_multiclass.generate(cfg, self.ctx.seed,
                                          rows + int(cfg["holdout_rows"]))
        self.train_gen = gen.slice(0, rows)
        self.holdout_gen = gen.slice(rows, gen.rows)
        self.table = workflows.table_of(self.train_gen, cfg["label"])
        self.units_per_op = float(rows)
        self.classes = len(cfg["label_rule"]["source_counts"])
        self.prepare_op()
        self.op()
        self.warm_report = self.reports.pop()

    # -- the reference's view of the last model ------------------------------
    def _slots(self):
        _, names, held, _, _ = self.compared
        return (workflows.slots_of(held[names[0]]),
                workflows.slots_of(held[names[1]]))

    def _holdout_matrix(self) -> np.ndarray:
        """The reference's feature matrix of the held-out rows."""
        gen = self.compared[3]
        return ref.feature_matrix(gen.columns, gen.types, *self._slots())

    def _reference_inputs(self, reg: float):
        """The reference's matrices of the rows the selector fits on and of
        the held-out rows, and its fit of the L2 point ``reg`` (kept: the
        controls compare with the same fit)."""
        if getattr(self, "reference_fit", (None,))[0] == reg:
            return self.reference_fit[1:]
        gen, sel = self.train_gen, self.config["workflow"]["selector"]
        rows, _ = ref.reserved_split(
            gen.rows, sel.get("reserve_test_fraction", 0.1),
            sel.get("seed", 42))
        slots = self._slots()
        X = ref.feature_matrix({k: v[rows] for k, v in gen.columns.items()},
                               gen.types, *slots)
        y = gen.label[rows].astype(np.int64)
        X_hold = self._holdout_matrix()
        start = self._winner_start(reg)
        fit = ref.fit_softmax(X, y, reg, self.classes, start=start)
        if start is not None and fit["grad_max"] >= 1e-10:
            # a start that is no optimum's neighbour: from the class shares
            fit = ref.fit_softmax(X, y, reg, self.classes)
        self.ctx.log(f"reference: softmax regParam {reg} on {len(rows)} rows, "
                     f"{fit['iterations']} Newton steps, largest gradient "
                     f"entry {fit['grad_max']:.3g}")
        self.reference_fit = (reg, X, y, X_hold, fit)
        self.reference_cv = None
        return self.reference_fit[1:]

    def _is_point(self, reg: float) -> bool:
        family, _ = workflows.fitted_of(self.compared[0])
        hyper = self.reports[-1]["hyper_dict"]
        return (family == ref.LR and not hyper.get("elasticNetParam")
                and abs(float(hyper["regParam"]) - reg) <= 1e-6 * reg)

    def _winner_start(self, reg: float) -> Optional[Tuple[np.ndarray,
                                                          np.ndarray]]:
        """Where the reference's Newton steps start: at the winner's refit
        where that is the point compared (the optimum is unique and the fit
        runs until its gradient vanishes, so the start saves steps and
        decides nothing), else at the class shares."""
        if not self._is_point(reg):
            return None
        _, params = workflows.fitted_of(self.compared[0])
        return (np.asarray(params["W"], dtype=np.float64),
                np.asarray(params["b"], dtype=np.float64))

    # -- the comparisons -----------------------------------------------------
    def compare_scores(self, precision: str = "f32") -> List[Check]:
        """Feature vector, all C probabilities and the held-out weighted F1
        of the program against the reference's (``bf16``: the control in
        the program's place, against the reference itself)."""
        model, names, held, gen, limits = self.compared
        family, params = workflows.fitted_of(model)
        X = self._holdout_matrix()
        want = ref.class_probs(family, params, X, self.classes)
        y = gen.label.astype(np.int64)
        if precision == "f32":
            got_X = np.asarray(held[names[1]].values, dtype=np.float32)
            got = class_probabilities(held[names[2]], self.classes)
            pred = workflows.prediction_part(held[names[2]], "prediction")
        else:
            got_X = ref.to_bf16(X)
            got = ref.class_probs(family, params, X, self.classes, precision)
            pred = got.argmax(axis=1)
        return [
            Check("feature_vector_max_abs_diff", _diff(got_X, X),
                  limits.get("feature_vector_max_abs_diff")),
            Check("prob_max_abs_diff", _diff(got, want),
                  limits.get("prob_max_abs_diff")),
            Check("prob_mean_abs_diff", float(np.abs(got - want).mean())
                  if got.shape == want.shape else float("nan"), None),
            Check("probs_finite", float(np.isfinite(got).all()), 1.0, "min"),
            Check("weighted_f1", ref.weighted_f1(pred, y, self.classes),
                  limits.get("weighted_f1_floor"), "min")]

    def _refit_checks(self, fit, X_hold, W, b, prob=None) -> List[Check]:
        """A fit's class-centred coefficients (standardised units) and
        held-out probabilities against the reference's optimum."""
        limits = self.compared[4]
        Wc, bc = ref.centred(W, b)
        if prob is None:
            prob = ref.softmax_prob(X_hold, W, b)
        return [
            Check("refit_coef_max_abs_diff",
                  _diff(Wc * fit["std"][:, None],
                        fit["W"] * fit["std"][:, None]),
                  limits.get("refit_coef_max_abs_diff")),
            Check("refit_intercept_max_abs_diff", _diff(bc, fit["b"]), None),
            Check("refit_prob_max_abs_diff",
                  _diff(prob, ref.softmax_prob(X_hold, fit["W"], fit["b"])),
                  limits.get("refit_prob_max_abs_diff"))]

    def compare_training(self, control: Optional[str] = None) -> List[Check]:
        """What the timed train fitted against the reference's own training
        of it: the softmax regression of the sweep's best L2 point, in
        float64 at its optimum on the rows the stock selector fits on.

        * ``refit_coef_max_abs_diff``, ``refit_prob_max_abs_diff``: the
          winner's refit against that optimum, where that point is the
          winner (said in the output where it is not);
        * ``cv_metric_abs_diff``: the sweep's reported mean F1 of that point
          against the reference's k-fold weighted F1 (whoever wins).

        ``control``: ``"bf16"`` (the reference's fit with bfloat16 features
        and temporaries) or ``"adam"`` (200 Adam steps at rate 0.1, the
        program's schedule before PR 26) stands in the refit's place."""
        model, names, held, _, limits = self.compared
        if not any(k in limits for k in TRAINING_LIMITS):
            return []
        report = self.reports[-1]
        sel = self.config["workflow"]["selector"]
        point = common._lr_point(report)
        if point is None:
            self.ctx.log("no L2 logistic point in the sweep: training is "
                         "not compared")
            return []
        reg, reported = point
        X, y, X_hold, fit = self._reference_inputs(reg)
        checks: List[Check] = []
        if control == "bf16":
            low = ref.fit_softmax(X, y, reg, self.classes, "bf16",
                                  max_iter=4, cg_iter=15)
            return self._refit_checks(fit, X_hold, low["W"], low["b"])
        if control == "adam":
            low = ref.adam_softmax(X, y, reg, self.classes)
            return self._refit_checks(fit, X_hold, low["W"], low["b"])
        if self._is_point(reg):
            _, params = workflows.fitted_of(model)
            checks += self._refit_checks(
                fit, X_hold, np.asarray(params["W"], dtype=np.float64),
                np.asarray(params["b"], dtype=np.float64),
                class_probabilities(held[names[2]], self.classes))
        else:
            self.ctx.log(f"winner {report['family']} {report['hyper']}: its "
                         f"refit has no plain form here; the sweep's "
                         f"logistic point is compared")
        if (sel["validation"] == "cross_validation"
                and report["metric_name"] == "F1"):
            if self.reference_cv is None:
                self.reference_cv = ref.cv_f1(
                    X, y, reg, self.classes, int(sel.get("folds", 3)),
                    self.ctx.seed, (fit["W"], fit["b"]))
            checks.append(Check("cv_metric_abs_diff",
                                abs(reported - self.reference_cv),
                                limits.get("cv_metric_abs_diff")))
            self.ctx.log(f"validation F1 of logistic regParam {reg}: "
                         f"{reported!r} against the reference's "
                         f"{self.reference_cv!r}")
        return checks

    def program_control(self) -> List[Check]:
        """The training numbers of one more train with the program's own
        lower-precision path in the refit's place (a control for the
        limits; no benchmark run does this)."""
        with workflows.refit_through_sweep_path():
            self.prepare_op()
            self.op()
        self._score_holdout()
        return self.compare_training()

    def controls(self) -> Dict[str, List[Check]]:
        """The checks with each control in the program's place, by the
        control's name; a sound limit fails the numbers meant for it."""
        return {
            "bf16 reference": (self.compare_training("bf16")
                               + self.compare_scores("bf16")),
            "adam 200 x 0.1": self.compare_training("adam"),
            "program's sweep path": self.program_control()}

    def check(self) -> List[Check]:
        cfg, limits = self.config, self.config["check"]
        last = self.reports[-1]
        self.ctx.log(
            f"winner {last['family']} {last['hyper']} metric "
            f"{last['metric']!r}; winners of the window: "
            f"{sorted({(r['family'], r['hyper']) for r in self.reports})}")
        cut = workflows.selected_model(self.model).summary.splitter_summary
        checks = [
            Check("fits", float(min(r["fits"] for r in self.reports)),
                  float(cfg["workflow"]["expected_fits"]), "min"),
            Check("fits_finite",
                  float(all(r["finite"] for r in self.reports)), 1.0, "min"),
            Check("quarantined_fits",
                  float(sum(r["quarantined"] for r in self.reports)), 0.0),
            Check("model_fault_sections",
                  float(len(workflows.model_faults(self.model))), 0.0),
            Check("classes_kept", float(len(cut.get("labelsKept", []))),
                  float(self.classes), "min"),
            Check("classes_dropped", float(len(cut.get("labelsDropped",
                                                       [0]))), 0.0)]
        held_table = self._score_holdout()
        names = self.compared[1]
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
