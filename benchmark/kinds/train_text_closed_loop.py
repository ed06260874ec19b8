"""One caller trains on the same in-memory table again and again, and the
table has a FREE-TEXT column: ``train_closed_loop``'s loop (rows from
``datagen_text``), with the feature vector held by ``reference_text.py``.

What ``correct`` compares, after the window:

* ``feature_vector_max_abs_diff``, exact: the vector the model reads on the
  held-out rows (the scoring path) against the reference's, recomputed from
  the raw rows by the stated rule (tokenise, crc32, count; pivots; circular
  periods); and ``train_vector_max_abs_diff``, exact: ``sampled_rows`` rows
  drawn from the seed out of the combined matrix that the window's LAST
  train left on the chip (``model.train_table``: what the timed path itself
  produced) against the reference's rows of the same index;
* ``text_path_native`` and ``text_rows_native_off``: the program's own
  ``text.hash`` span of the held-out score says which path the documents
  took and how many rows the Python tokenizer took; the native kernel must
  have run and the rows it kept must be the reference's count of ASCII
  documents. A run that tokenised every row in Python is not this cell;
* ``score_max_abs_diff``: the winner's class-1 score against
  ``reference.class1_score`` from its fitted parameters over the reference's
  matrix; ``auroc`` on the held-out rows;
* ``refit_coef_max_abs_diff``, ``refit_score_max_abs_diff``,
  ``cv_metric_abs_diff``: ``common.compare_training`` (the sweep's best L2
  logistic point refitted in float64 to its optimum on the rows the selector
  fits on, from the reference's matrix; the fit is taken only at its
  optimum, as the mesh cell's is). A winner that is not that point leaves
  the two refit numbers out and says so;
* ``fits``, finiteness, no quarantine, no fault section,
  ``planned_vs_eager_max_abs_diff``: as ``train_closed_loop``.

``controls()`` puts a control in the program's place for each number (read
by ``benchmark/tools/controls.py`` and the tests, never by a benchmark run).
"""
from __future__ import annotations

import gc
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import datagen_text, reference, reference_text, workflows
from ..harness import Check, Context
from . import common, train_closed_loop
from .train_forest_closed_loop import Loop as _ForestLoop
from .train_mesh_closed_loop import GRADIENT_LIMIT, _gradient, fitted_rows
# (parent, indicator, descriptor) a slot: the descriptor says which hash bin or
# which circular period a column is
from .train_regression_closed_loop import _diff, slots_of

#: the controls of the hashed block: ``reference_text.hash_counts`` options
BLOCK_CONTROLS = {"other_modulus": {"modulus": 511},
                  "no_lower_case": {"lower": False},
                  "binary_counts": {"binary": True}}


class Loop(train_closed_loop.Loop):
    def __init__(self, ctx: Context):
        super().__init__(ctx)
        # The cell states which path the tokenizer took (text_path) from the
        # program's own text.hash span. A program whose text stage says
        # nothing of it (before PR 41) cannot run the cell: it stops here,
        # before any row is made.
        from transmogrifai_tpu.impl.feature import vectorizers
        if not hasattr(vectorizers, "_tokenize_hash_counted"):
            raise SystemExit(
                "train_text_closed_loop: this program's SmartTextVectorizer "
                "does not say which path its documents took (no text.hash "
                "span: impl/feature/vectorizers.py before PR 41); the cell "
                "cannot hold text_path on it")
        self.reference_fit: Optional[tuple] = None
        self.reference_cv: Optional[float] = None
        self._kept: Optional[tuple] = None

    def setup(self) -> None:
        cfg = self.config
        rows = int(self.traffic.get("rows") or cfg["rows"])
        gen = datagen_text.generate(cfg, self.ctx.seed,
                                    rows + int(cfg["holdout_rows"]))
        self.train_gen = gen.slice(0, rows)
        self.holdout_gen = gen.slice(rows, gen.rows)
        self.table = workflows.table_of(self.train_gen, cfg["label"])
        self.units_per_op = float(rows)
        self.prepare_op()
        self.op()
        self.warm_report = self.reports.pop()
        # train_forest_closed_loop's reason: the loop holds the previous
        # model's table while it trains, so the third train is the first
        # that runs wholly in memory the process already holds
        for _ in range(int(self.traffic.get("warm_ops", 1)) - 1):
            self.prepare_op()
            gc.collect()
            self.op()
            self.reports.pop()

    # -- the reference's view of the last model ------------------------------
    def _score_holdout(self):
        """``train_closed_loop``'s, with the program's spans on around the
        held-out score: its ``text.hash`` spans say which path the documents
        took. Returns the held-out table."""
        from transmogrifai_tpu.observability import trace as obs_trace
        workflows.enable_spans(True)
        try:
            held_table = super()._score_holdout()
            spans, _ = workflows.finished_spans()
        finally:
            workflows.enable_spans(False)
            obs_trace.tracer().clear()      # nothing of it stays behind
        self.text_spans = [s for s in spans if s.name == "text.hash"]
        return held_table

    def _matrices(self) -> None:
        """The reference's matrices of the last model: the whole vector of
        the training and the held-out rows (before the checker), the columns
        the checker kept of them, and the float64 logistic fit of the
        sweep's best L2 point on the rows the selector fits on (kept where
        ``common.compare_training`` looks for it)."""
        _, names, held, hold_gen, _ = self.compared
        full, kept = slots_of(held[names[0]]), slots_of(held[names[1]])
        if self._kept != (full, kept):
            gen = self.train_gen
            at = {s: j for j, s in enumerate(full)}
            cols = np.array([at[s] for s in kept])
            self.X_train_full = reference_text.feature_matrix(
                gen.columns, gen.types, full, full)
            self.X_hold_full = reference_text.feature_matrix(
                hold_gen.columns, hold_gen.types, full, full)
            self.fit_rows = fitted_rows(gen.rows,
                                        self.config["workflow"]["selector"])
            self.X_fit = self.X_train_full[self.fit_rows][:, cols]
            self.y_fit = gen.label[self.fit_rows]
            self.X_hold = self.X_hold_full[:, cols]
            self._kept, self.reference_fit = (full, kept), None
            self.ctx.log(f"reference_text: {len(full)} derived columns, "
                         f"{len(kept)} after the checker")
        point = common._lr_point(self.reports[-1])
        if point is not None and (self.reference_fit or (None,))[0] != point[0]:
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

    def _train_sample(self) -> Tuple[np.ndarray, np.ndarray]:
        """(rows drawn from the seed, those rows of the combined matrix that
        the last train left on the device)."""
        import jax.numpy as jnp
        vector = self.model.train_table[self.compared[1][0]].values
        n = min(int(self.config["check"].get("sampled_rows", 65536)),
                int(vector.shape[0]))
        rows = np.sort(np.random.default_rng(
            [int(self.ctx.seed), 13]).choice(int(vector.shape[0]), n,
                                             replace=False))
        return rows, np.asarray(jnp.take(jnp.asarray(vector), rows, axis=0),
                                dtype=np.float32)

    # -- the comparisons ------------------------------------------------------
    def vector_checks(self, **hash_options) -> List[Check]:
        """The program's vector against the reference's; a control hands in
        ``reference_text.hash_counts`` options and the reference is then
        recomputed with them, on the held-out rows."""
        _, names, held, hold_gen, limits = self.compared
        limit = limits.get("feature_vector_max_abs_diff")
        got = np.asarray(held[names[1]].values, dtype=np.float32)
        if hash_options:
            full, kept = self._kept
            return [Check("feature_vector_max_abs_diff", _diff(
                got, reference_text.feature_matrix(
                    hold_gen.columns, hold_gen.types, full, kept,
                    **hash_options)), limit)]
        rows, sampled = self._train_sample()
        return [Check("feature_vector_max_abs_diff",
                      _diff(got, self.X_hold), limit),
                Check("train_vector_max_abs_diff",
                      _diff(sampled, self.X_train_full[rows]), limit)]

    def path_checks(self) -> List[Check]:
        docs = [c for name, c in self.holdout_gen.columns.items()
                if self.holdout_gen.types[name] == "Text"]
        ascii_rows = sum(d is not None and d.isascii()
                         for col in docs for d in col)
        rows = sum(int(s.attrs.get("rows", 0)) for s in self.text_spans)
        py_rows = sum(int(s.attrs.get("pyRows", 0)) for s in self.text_spans)
        paths = sorted({str(s.attrs.get("path")) for s in self.text_spans})
        self.ctx.log(f"text_path {'+'.join(paths) or 'none'}: of {rows} "
                     f"held-out documents the Python tokenizer took "
                     f"{py_rows}, the reference counts "
                     f"{rows - ascii_rows} that are not ASCII")
        return [Check("text_path_native", float(paths == ["native"]), 1.0,
                      "min"),
                Check("text_rows_native", float(rows - py_rows), None),
                Check("text_rows_native_off",
                      float(abs(rows - py_rows - ascii_rows)), 0.0)]

    def score_checks(self, precision: str = "f32") -> List[Check]:
        """The winner's class-1 score on the held-out rows against the
        reference's from its fitted parameters; ``precision="bf16"`` puts
        the reference in bfloat16 in the program's place."""
        model, names, held, hold_gen, limits = self.compared
        family, params = workflows.fitted_of(model)
        ref = reference.class1_score(family, params, self.X_hold)
        if precision == "f32":
            got = workflows.prediction_part(held[names[2]],
                                            reference.score_key(family))
        else:
            got = reference.class1_score(family, params, self.X_hold,
                                         precision)
        return [
            Check("score_max_abs_diff", float(np.abs(got - ref).max()),
                  limits.get("score_max_abs_diff")),
            Check("score_mean_abs_diff", float(np.abs(got - ref).mean()),
                  None),
            Check("scores_finite", float(np.isfinite(got).all()), 1.0,
                  "min"),
            Check("auroc", reference.auroc(got, hold_gen.label),
                  limits.get("auroc_floor"), "min"),
            Check("auroc_of_label_rule",
                  reference.auroc(hold_gen.true_prob, hold_gen.label), None)]

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
        ]
        held_table = self._score_holdout()
        names = self.compared[1]
        self._matrices()
        checks += self.path_checks() + self.vector_checks()
        checks += self.score_checks()
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
        ``check()``: the reference's fit and score in bfloat16; a hash by
        another modulus, a tokenizer that does not lower-case and binary
        counts in the block's place; one fold of three scored the wrong way
        round; one more train whose refit runs the program's sweep path."""
        out: Dict[str, List[Check]] = {}
        hashed = [j for j, (p, _, d) in enumerate(self._kept[1])
                  if self.holdout_gen.types[p] == "Text" and d]
        block = self.X_hold[:, hashed]
        moved = _diff(reference.to_bf16(self.X_hold), self.X_hold)
        self.ctx.log(f"bfloat16 moves the reference's vector by {moved!r} "
                     f"(the timestamp's sines and cosines) and its hashed "
                     f"block of {len(hashed)} columns by "
                     f"{_diff(reference.to_bf16(block), block)!r}: counts "
                     f"are small integers, exact in bfloat16")
        out["bf16"] = (common.compare_training(self, "bf16")
                       + self.score_checks("bf16"))
        for name, options in BLOCK_CONTROLS.items():
            out[name] = self.vector_checks(**options)
        if self.reference_cv is not None:
            out["fold_reversed"] = [Check(
                "cv_metric_abs_diff",
                # the forest kind's control, as it stands: it reads this
                # loop's reference_fit, configuration and seed, no more
                abs(_ForestLoop._cv_one_fold_reversed(self)
                    - self.reference_cv),
                self.config["check"].get("cv_metric_abs_diff"))]
        kept = (self.model, self.built, self.compared, self.text_spans)
        try:
            with workflows.refit_through_sweep_path():
                self.prepare_op()
                self.op()
            self._score_holdout()
            self._matrices()
            out["sweep_path_refit"] = common.compare_training(self)
            self.reports.pop()
        finally:
            self.model, self.built, self.compared, self.text_spans = kept
        return out
