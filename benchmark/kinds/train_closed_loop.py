"""One caller trains on the same in-memory table again and again."""
from __future__ import annotations

from typing import Any, Dict, List

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
        self.model = None
        self.built = None
        self.reports: List[Dict[str, Any]] = []

    # -- set-up: data from the seed, the table once, one warm train --------
    def setup(self) -> None:
        cfg = self.config
        rows = int(self.traffic.get("rows") or cfg["rows"])
        gen = datagen.generate(cfg, self.ctx.seed,
                               rows + int(cfg["holdout_rows"]))
        self.train_gen = gen.slice(0, rows)
        self.holdout_gen = gen.slice(rows, gen.rows)
        self.table = workflows.table_of(self.train_gen, cfg["label"])
        self.units_per_op = float(rows)
        self.prepare_op()
        self.op()
        self.warm_report = self.reports.pop()

    def prepare_op(self) -> None:
        self.built = workflows.build_workflow(self.config, self.table)

    def op(self) -> None:
        model = self.built.workflow.train()
        workflows.wait_for_model(model)
        self.model = model
        self.reports.append(workflows.sweep_report(model,
                                                   self.built.selector))

    def end_to_end(self, ops):
        return common.reported(self.traffic, ops, self.units_per_op)

    # -- after the window -----------------------------------------------------
    def _score_holdout(self):
        """The last model's scores of the held-out rows, kept for the
        comparisons; returns the held-out table."""
        names = (self.built.vector.name, self.built.checked.name,
                 self.built.prediction.name)
        held_table = workflows.table_of(self.holdout_gen,
                                        self.config["label"])
        held = self.model.score(table=held_table)
        self.compared = (self.model, names, held, self.holdout_gen,
                         self.config["check"])
        return held_table

    def program_control(self) -> List[Check]:
        """The training numbers of one more train with the program's own
        lower-precision path in the refit's place (a control for the
        limits; no benchmark run does this)."""
        with workflows.refit_through_sweep_path():
            self.prepare_op()
            self.op()
        self._score_holdout()
        return common.compare_training(self)

    def check(self) -> List[Check]:
        cfg, limits = self.config, self.config["check"]
        last = self.reports[-1]
        self.ctx.log(f"winner {last['family']} {last['hyper']} metric "
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
                  float(len(workflows.model_faults(self.model))), 0.0),
        ]
        held_table = self._score_holdout()
        names = self.compared[1]
        checks += common.compare_with_reference(*self.compared)
        checks += common.compare_training(self)
        n = min(int(limits.get("parity_rows", 10000)),
                self.holdout_gen.rows)
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
