"""One caller scores the whole table again and again with a model trained
in set-up through the normal entry points."""
from __future__ import annotations

from typing import List

import numpy as np

from .. import datagen, workflows
from ..harness import Check, Context
from . import common


class Loop:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.config = ctx.cell.config
        self.traffic = ctx.cell.traffic
        self.failed_ops = 0
        self.first_values = None
        self.last = None

    # -- set-up: data, the model, one warm score ----------------------------
    def setup(self) -> None:
        cfg = self.config
        rows = int(self.traffic.get("rows") or cfg["rows"])
        if rows > int(cfg["rows"]):
            raise ValueError(f"traffic scores {rows} rows, the "
                             f"configuration has {cfg['rows']}")
        self.gen = datagen.generate(cfg, self.ctx.seed, rows)
        self.table = workflows.table_of(self.gen, cfg["label"])
        self.units_per_op = float(rows)
        train_rows = min(int(cfg["workflow"]["train_rows"]), rows)
        train_table = workflows.table_of(self.gen.slice(0, train_rows),
                                         cfg["label"])
        self.built = workflows.build_workflow(cfg, train_table)
        self.model = self.built.workflow.train()
        workflows.wait_for_model(self.model)
        self.report = workflows.sweep_report(self.model,
                                             self.built.selector)
        self.pred_name = self.built.prediction.name
        self.op()
        self.first_values, self.last = self.last_values, None

    def prepare_op(self) -> None:
        self.last = None        # the previous result is the caller's to drop

    def op(self) -> None:
        scored = self.model.score(table=self.table)
        # a user's result: the prediction column on the host
        self.last_values = np.asarray(scored[self.pred_name].values)
        self.last = scored

    def end_to_end(self, ops):
        return common.reported(self.traffic, ops, self.units_per_op)

    # -- after the window -----------------------------------------------------
    def check(self) -> List[Check]:
        cfg, limits = self.config, self.config["check"]
        self.ctx.log(f"model {self.report['family']} {self.report['hyper']}")
        n = self.gen.rows
        rng = np.random.default_rng([self.ctx.seed, 424242])
        take = np.sort(rng.choice(n, size=min(int(limits["sample_rows"]), n),
                                  replace=False))
        names = (self.built.vector.name, self.built.checked.name,
                 self.pred_name)
        sample = self.last.take(take)
        sample_gen = datagen.Generated(
            {k: v[take] for k, v in self.gen.columns.items()},
            dict(self.gen.types), self.gen.label[take], None)
        checks = [
            Check("rows_scored", float(self.last_values.shape[0]), float(n),
                  "min"),
            Check("all_scores_finite",
                  float(np.isfinite(self.last_values).all()), 1.0, "min"),
            Check("fits", float(self.report["fits"]),
                  float(cfg["workflow"]["expected_fits"]), "min"),
            Check("model_fault_sections",
                  float(len(workflows.model_faults(self.model))), 0.0),
        ]
        self.compared = (self.model, names, sample, sample_gen, limits)
        checks += common.compare_with_reference(*self.compared)
        checks.append(Check(
            "first_vs_last_score_max_abs_diff",
            float(np.abs(self.first_values - self.last_values).max()), 0.0))
        checks.append(Check("fault_kinds_counted",
                            float(len(workflows.fault_counts())), 0.0))
        return checks
