"""One caller trains on the same in-memory table again and again, and a
TREE ENSEMBLE wins: ``train_closed_loop``'s loop (rows from
``datagen_higgs``), with the winner held by ``reference_forest.py``.

``train_closed_loop``'s comparisons retrain a logistic winner; a forest has
no optimum to solve for. What a stock selection does with a tree winner is
held in its parts instead:

* ``score_max_abs_diff``: the winner's class-1 score on the held-out rows
  against the numpy descent of its fitted trees on raw float32 values;
* ``refit_leaf_max_abs_diff``: the refit's exact part, retrained. A forest:
  the rows the selector fitted on (``train_mesh_closed_loop.fitted_rows``:
  its balancer's sample) routed down every tree in numpy and every leaf's
  value recomputed from the rows' labels in float64. A boosted winner, whose
  refit trains on the split-search sample alone: its rounds retrained on
  those rows, a round's gradients from the reference's own running score;
* ``refit_split_gain_rel_diff``: the grower at refit size, on the rows the
  trees were grown on (the strided sample of the padded fit matrix). A
  boosted winner: at EVERY node that splits, in every round, the split the
  tables state against the best second-order gain that any column and bin
  gives on the rows the reference routes to that node at that round's
  gradients (the largest shortfall; ``refit_split_gain_mean_rel_diff`` the
  mean over the nodes). A forest: the root split of every tree, the gain its
  bin leaves on the table in its own column, without the trees' bootstrap
  weights, which come from the program's own random stream: a sound grower
  reads a small shortfall, not zero;
* ``refit_edges_max_rel_diff``: the fit's bin-edge table against the one the
  reference rebuilds from the grown rows by the stated rule (the 31 inner
  quantiles of the split-search sample, its pad rows included);
* ``cv_metric_abs_diff``: the sweep's best L2 logistic point against the
  reference's own 3-fold fit (``common.compare_training``, on the rows the
  selector fits on, from a fit held to its optimum as the mesh cell's is);
* ``winner_is_stated_family``, ``winner_max_depth_off``: the family and the
  ``maxDepth`` the configuration states, in every train of the window (a
  winner that flips refits another program and ``train_s`` flips with it);
  ``winner_depth_margin``, printed without a limit: how far the stated depth
  led the family's other depths in the sweep's validation metric;
* ``auroc`` on the held-out rows, and ``linear_auroc_gap``: how far the
  reference's float64 logistic fit stays under the winner there, the
  configuration's reason for being.

``controls()`` puts a control in the program's place for each number (read
by ``benchmark/tools/controls.py`` and the tests, never by a benchmark run).
"""
from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from .. import datagen_higgs, forest_readers  # noqa: F401  (reader kinds)
from .. import reference, reference_forest, workflows
from ..harness import Check, Context
from . import common, train_closed_loop
from .train_mesh_closed_loop import GRADIENT_LIMIT, _gradient, fitted_rows

TREE_FAMILIES = tuple(reference_forest.SCORES)


class Loop(train_closed_loop.Loop):
    def __init__(self, ctx: Context):
        super().__init__(ctx)
        self.sums = None         # the reference's leaf sums of the last model

    def setup(self) -> None:
        cfg = self.config
        rows = int(self.traffic.get("rows") or cfg["rows"])
        gen = datagen_higgs.generate(cfg, self.ctx.seed,
                                     rows + int(cfg["holdout_rows"]))
        self.train_gen = gen.slice(0, rows)
        self.holdout_gen = gen.slice(rows, gen.rows)
        self.table = workflows.table_of(self.train_gen, cfg["label"])
        self.units_per_op = float(rows)
        self.prepare_op()
        self.op()
        self.warm_report = self.reports.pop()
        # the closed loop's own state before the window opens: a train
        # starts with the previous model's table alive and ends with two,
        # so the SECOND train is the first to reach the loop's high-water
        # mark of host memory (fresh pages: 2-4 s on the chip's host) and
        # the third the first to run wholly in memory the process already
        # holds; the traffic's ``warm_ops`` says how many run in set-up
        for _ in range(int(self.traffic.get("warm_ops", 1)) - 1):
            self.prepare_op()
            gc.collect()
            self.op()
            self.reports.pop()

    # -- what the comparisons read ------------------------------------------
    def _matrices(self):
        """The reference's feature matrix of the held-out rows and of the
        rows the selector fitted on, their labels, and the logistic fit of
        the sweep's best L2 point on the latter (kept where
        ``common.compare_training`` looks for it)."""
        _, names, held, hold_gen, _ = self.compared
        slots = (workflows.slots_of(held[names[0]]),
                 workflows.slots_of(held[names[1]]))
        if getattr(self, "fit_rows", None) is None:
            gen = self.train_gen
            self.fit_rows = fitted_rows(gen.rows,
                                        self.config["workflow"]["selector"])
            self.X_fit = reference.feature_matrix(
                {k: v[self.fit_rows] for k, v in gen.columns.items()},
                gen.types, *slots)
            self.y_fit = gen.label[self.fit_rows]
            self.X_hold = reference.feature_matrix(
                hold_gen.columns, hold_gen.types, *slots)
        point = common._lr_point(self.reports[-1])
        if point is not None and getattr(self, "reference_fit",
                                         (None,))[0] != point[0]:
            reg = point[0]
            fit = reference.fit_logistic(self.X_fit, self.y_fit, reg)
            grad = _gradient(self.X_fit, self.y_fit, reg, fit)
            self.ctx.log(f"reference: logistic regParam {reg} on "
                         f"{len(self.fit_rows)} rows, {fit['iterations']} "
                         f"Newton steps, largest gradient entry {grad!r}")
            if not grad <= GRADIENT_LIMIT:
                raise RuntimeError(
                    f"the reference's fit is not at its optimum (gradient "
                    f"{grad!r} > {GRADIENT_LIMIT}): nothing to compare with")
            self.reference_fit = (reg, self.X_fit, self.y_fit, self.X_hold,
                                  fit)
            self.reference_cv = None

    def _grown(self):
        """Rows (of the fitted ones) the refit's trees were grown on."""
        sel = self.config["workflow"]["selector"]
        return reference_forest.grown_rows(
            len(self.fit_rows), int(sel["refit_padded_rows"]),
            int(sel["split_search_sample"]))

    def forest_checks(self, params=None, scores=None, label=None,
                      frozen=False) -> List[Check]:
        """The winner's numbers against ``reference_forest``. A control
        hands in other ``params`` (the fitted tables the reference reads as
        the program's), other ``scores`` (in the program's place), the
        ``label`` its train saw, or a boosted reference whose running score
        is ``frozen``."""
        model, names, held, hold_gen, limits = self.compared
        family, own = workflows.fitted_of(model)
        params = own if params is None else params
        got = workflows.prediction_part(held[names[2]],
                                        reference.score_key(family))
        got = got if scores is None else scores
        auc = reference.auroc(got, hold_gen.label)
        checks = [
            Check("scores_finite", float(np.isfinite(got).all()), 1.0, "min"),
            Check("auroc", auc, limits.get("auroc_floor"), "min"),
            Check("auroc_of_label_rule",
                  reference.auroc(hold_gen.true_prob, hold_gen.label), None)]
        if getattr(self, "reference_fit", None) is not None:
            linear = reference.auroc(reference.logistic_prob(
                self.X_hold, self.reference_fit[4]), hold_gen.label)
            self.ctx.log(f"held-out AuROC: winner {auc!r}, the reference's "
                         f"float64 logistic fit {linear!r}")
            checks.append(Check("linear_auroc_gap", auc - linear,
                                limits.get("linear_auroc_gap_floor"), "min"))
        if family not in TREE_FAMILIES:
            self.ctx.log(f"winner {family}: no tree ensemble, nothing for "
                         f"reference_forest to hold")
            return checks
        if getattr(self, "_ref", (None,))[0] is not model:    # once a model
            self._ref = (model, reference_forest.class1_score(
                family, own, self.X_hold))
        ref = self._ref[1]
        checks += [
            Check("score_max_abs_diff", float(np.abs(got - ref).max()),
                  limits.get("score_max_abs_diff")),
            Check("score_mean_abs_diff", float(np.abs(got - ref).mean()),
                  None)]
        y_fit = self.y_fit if label is None else label
        hyper = self.reports[-1]["hyper_dict"]
        min_rows = float(hyper.get("minInstancesPerNode", 1.0))
        grown = self._grown()
        sel = self.config["workflow"]["selector"]
        padded, sample = (int(sel["refit_padded_rows"]),
                          int(sel["split_search_sample"]))
        checks.append(Check(
            "refit_edges_max_rel_diff", reference_forest.edges_rel_diff(
                params, reference_forest.sample_edges(
                    self.X_fit[grown], len(self.fit_rows), padded, sample)),
            limits.get("refit_edges_max_rel_diff")))
        if family == "OpGBTClassifier":
            # boosting trains on the split-search sample alone: its rounds
            # are retrained there, from the reference's own running score,
            # and every node's split is held to the rows it finds there
            got = reference_forest.boosted_rounds(
                params, self.X_fit[grown], y_fit[grown], min_rows,
                float(hyper.get("lambda", 0.0)), frozen,
                row_weight=max(padded / sample, 1.0))
            self.ctx.log(
                f"reference_forest: {got['leaves_compared']} leaves of "
                f"{got['roots_compared']} rounds retrained on {len(grown)} "
                f"grown rows; {got['splits_compared']} splits compared, "
                f"{got['splits_short']} short of the best, mean shortfall "
                f"{got['split_shortfall_mean']!r}, the roots' largest "
                f"{got['root_shortfall_max']!r}")
            return checks + self._refit_checks(
                got["leaf_max_abs_diff"], got["leaves_compared"],
                got["split_shortfall_max"], got["roots_compared"]) + [
                Check("refit_split_gain_mean_rel_diff",
                      got["split_shortfall_mean"],
                      limits.get("refit_split_gain_mean_rel_diff")),
                Check("refit_splits_compared", float(got["splits_compared"]),
                      limits.get("refit_splits_compared_floor"), "min")]
        if params is own and label is None and self.sums is not None:
            sums = self.sums
        else:
            sums = reference_forest.leaf_class_sums(
                params, self.X_fit, y_fit, np.ones(len(y_fit)))
            if params is own and label is None:
                self.sums = sums
        diff, leaves = reference_forest.refit_leaves(params, sums, min_rows)
        worst, mean, roots = reference_forest.root_split_shortfall(
            params, self.X_fit[grown], y_fit[grown])
        self.ctx.log(f"reference_forest: {leaves} leaves of "
                     f"{reference_forest.n_trees(params)} trees compared on "
                     f"{len(self.fit_rows)} fitted rows; {roots} root splits "
                     f"on {len(grown)} grown rows, mean shortfall {mean!r}")
        return checks + self._refit_checks(diff, leaves, worst, roots)

    def _refit_checks(self, diff, leaves, worst, roots) -> List[Check]:
        limits = self.config["check"]
        return [
            Check("refit_leaf_max_abs_diff", diff,
                  limits.get("refit_leaf_max_abs_diff")),
            Check("refit_leaves_compared", float(leaves),
                  limits.get("refit_leaves_compared_floor"), "min"),
            Check("refit_split_gain_rel_diff", worst,
                  limits.get("refit_split_gain_rel_diff")),
            Check("refit_root_splits_compared", float(roots),
                  limits.get("refit_root_splits_compared_floor"), "min")]

    def winner_checks(self) -> List[Check]:
        stated = self.config["workflow"]["stated_winner"]
        off = [abs(float(r["hyper_dict"].get("maxDepth", -1.0))
                   - float(stated["maxDepth"])) for r in self.reports]
        return [
            Check("winner_is_stated_family",
                  float(all(r["family"] == stated["family"]
                            for r in self.reports)), 1.0, "min"),
            Check("winner_max_depth_off", float(max(off)), 0.0),
            Check("winner_depth_margin",
                  min(self._depth_margin(r, stated) for r in self.reports),
                  None)]

    @staticmethod
    def _depth_margin(report, stated) -> float:
        """The sweep's best validation metric at the stated family and
        ``maxDepth`` less its best at any other depth of that family: how
        far the stated depth led (negative where it did not). Printed
        without a limit: the exact checks above decide."""
        best: Dict[float, float] = {}
        for point, metric in zip(report["grids"].get(stated["family"], []),
                                 report["by_family"].get(stated["family"],
                                                         [])):
            depth = float(point.get("maxDepth", -1.0))
            best[depth] = max(best.get(depth, -np.inf), metric)
        own = best.pop(float(stated["maxDepth"]), None)
        if own is None or not best:
            return float("nan")
        return float(own - max(best.values()))

    def check(self) -> List[Check]:
        cfg, limits = self.config, self.config["check"]
        last = self.reports[-1]
        self.ctx.log(f"winner {last['family']} {last['hyper']} metric "
                     f"{last['metric']!r}; winners of the window: "
                     f"{sorted({(r['family'], r['hyper']) for r in self.reports})}")
        self.ctx.log("best point by family: " + ", ".join(
            f"{fam} {max(ms)!r}" for fam, ms in last["by_family"].items()))
        checks = [
            Check("fits", float(min(r["fits"] for r in self.reports)),
                  float(cfg["workflow"]["expected_fits"]), "min"),
            Check("fits_finite",
                  float(all(r["finite"] for r in self.reports)), 1.0, "min"),
            Check("quarantined_fits",
                  float(sum(r["quarantined"] for r in self.reports)), 0.0),
            Check("model_fault_sections",
                  float(len(workflows.model_faults(self.model))), 0.0),
        ] + self.winner_checks()
        held_table = self._score_holdout()
        self.sums = None
        self._matrices()
        model, names, held, _, _ = self.compared
        got_X = np.asarray(held[names[1]].values, dtype=np.float32)
        checks.append(Check(
            "feature_vector_max_abs_diff",
            float(np.abs(got_X - self.X_hold).max())
            if got_X.shape == self.X_hold.shape else float("nan"),
            limits.get("feature_vector_max_abs_diff")))
        checks += self.forest_checks()
        checks += common.compare_training(self)
        n = min(int(limits.get("parity_rows", 10000)), self.holdout_gen.rows)
        part = held_table.take(np.arange(n))
        planned = np.asarray(self.model.score(table=part)[names[2]].values)
        eager = np.asarray(workflows.score_eager(self.model,
                                                 part)[names[2]].values)
        checks.append(Check("planned_vs_eager_max_abs_diff",
                            float(np.abs(planned - eager).max()),
                            limits.get("planned_vs_eager_max_abs_diff")))
        checks.append(Check("fault_kinds_counted",
                            float(len(workflows.fault_counts())), 0.0))
        return checks

    # -- controls: never part of a benchmark run ------------------------------
    def controls(self) -> Dict[str, List[Check]]:
        """The same numbers with a control in the program's place, after
        ``check()``: the descent with rows and thresholds in bfloat16; for a
        forest leaf values from the split-search sample in the exact leaf
        pass's place, for a boosted winner rounds retrained with a running
        score that never moves; trees whose root splits lie four bins off;
        a train whose label was shuffled before the selector saw it."""
        model, names, held, hold_gen, _ = self.compared
        family, own = workflows.fitted_of(model)
        out: Dict[str, List[Check]] = {}
        out["bf16_descent"] = self.forest_checks(
            scores=reference_forest.class1_score(family, own, self.X_hold,
                                                 "bf16"))
        if family == "OpRandomForestClassifier":
            grown = self._grown()
            sample = reference_forest.leaf_class_sums(
                own, self.X_fit[grown], self.y_fit[grown],
                np.ones(len(grown)))
            out["sample_leaves"] = self.forest_checks(
                params=reference_forest.with_leaves_from(own, sample))
        elif family == "OpGBTClassifier":
            out["frozen_score"] = self.forest_checks(frozen=True)
        out["roots_moved"] = self.forest_checks(
            params=reference_forest.with_roots_moved(own, 4))
        if family == "OpGBTClassifier":
            out["splits_moved"] = self.forest_checks(
                params=reference_forest.with_splits_moved(own, 4, level=3))
        grown = self._grown()
        out["edges_without_pad_rows"] = [Check(
            "refit_edges_max_rel_diff", reference_forest.edges_rel_diff(
                own, reference_forest.sample_edges(
                    self.X_fit[grown], len(self.fit_rows), 0, 0)),
            self.config["check"].get("refit_edges_max_rel_diff"))]
        if getattr(self, "reference_cv", None) is not None:
            out["fold_reversed"] = [Check(
                "cv_metric_abs_diff",
                abs(self._cv_one_fold_reversed() - self.reference_cv),
                self.config["check"].get("cv_metric_abs_diff"))]
        out["shuffled_label"] = self._shuffled_label()
        return out

    def _cv_one_fold_reversed(self) -> float:
        """``reference.cv_aupr``'s own folds and fits of the sweep's best L2
        logistic point, with the first fold's scores the wrong way round:
        what a sweep that scores one fold of three wrongly would report."""
        reg, X, y, _, fit = self.reference_fit
        folds = int(self.config["workflow"]["selector"].get("folds", 3))
        perm = np.random.default_rng(
            [int(self.ctx.seed), 7]).permutation(X.shape[0])
        out = []
        for f in range(folds):
            val = np.zeros(X.shape[0], dtype=bool)
            val[perm[f::folds]] = True
            got = reference.fit_logistic(
                X[~val], y[~val], reg, "f64", (fit["coef"], fit["bias"]),
                max_iter=30, tol=1e-7)
            prob = reference.logistic_prob(X[val], got)
            out.append(reference.aupr(1.0 - prob if f == 0 else prob,
                                      y[val]))
        return float(np.mean(out))

    def _shuffled_label(self) -> List[Check]:
        """One more train on the same rows with the label column permuted:
        what it learnt is held against the true held-out labels."""
        kept = (self.table, self.model, self.built, self.compared, self.sums)
        gen = self.train_gen
        perm = np.random.default_rng([self.ctx.seed, 11]).permutation(
            gen.rows)
        shuffled = datagen_higgs.Generated(gen.columns, gen.types,
                                           gen.label[perm], None)
        try:
            self.table = workflows.table_of(shuffled, self.config["label"])
            self.prepare_op()
            self.op()
            self.reports.pop()
            self._score_holdout()
            checks = self.forest_checks(label=shuffled.label[self.fit_rows])
        finally:
            (self.table, self.model, self.built, self.compared,
             self.sums) = kept
        return checks
