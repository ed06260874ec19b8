"""One caller trains on the same in-memory table again and again, on one
host's chips: ``train_closed_loop`` with the workflow put on a mesh
(``OpWorkflow.with_mesh``) over the first ``chips`` devices.

Beyond ``train_closed_loop``'s checks, ``correct`` holds what makes the run
a run across chips (chip_smoke.py's four-chip checks, on the timed path):
the chips used, no downgrade by the program's cost model, the sweep's table
sharded over 'data' in equal shards, and every chip's peak at or above one
input shard.

The reference's training runs on the rows the selector fits on
(``fitted_rows``: of the nine million rows kept, the million of its
balancer's ``max_training_sample``), which ``common.compare_training``'s own
row rule (every row kept) is not: this kind computes ``reference.py``'s fit
on those rows first, from the reference's own start, holds it to its optimum
(``_gradient``), and hands it over where ``compare_training`` keeps it
(``reference_fit``; the k-fold metric and the controls it then computes
itself, on the same rows).

The kind needs the program's sharded placement (``parallel.sharded.take_rows``
and ``place_rows``): a program without it would gather and pad whole copies of
the table on every chip. It is refused at once, before any row is made.
"""
from __future__ import annotations

from typing import List

import numpy as np

from .. import mesh_readers  # noqa: F401  (registers the mesh reader kinds)
from .. import reference, workflows
from ..harness import Check, Context
from . import common, train_closed_loop

DOWNGRADES = "tg_mesh_downgrade_total"


def _needs(module, names) -> None:
    missing = [n for n in names if not hasattr(module, n)]
    if missing:
        raise SystemExit(
            f"benchmark: this program's {module.__name__} has no "
            f"{', '.join(missing)}: the mesh cell needs the sharded "
            f"placement of the PR that added it. No result.")


def fitted_rows(n: int, selector: dict) -> np.ndarray:
    """The rows of an ``n``-row table that the stock binary selector fits
    on, by its stated rules: ``reference.reserved_split``'s (it sets
    ``reserve_test_fraction`` of the rows aside), and of those at most
    ``max_training_sample``: its ``DataBalancer``, where the label's rarer
    value is no rarer than the balancer's target, keeps the sorted
    ``RandomState(seed).choice(rows, max_training_sample, replace=False)``
    of a larger split and every row of a smaller one."""
    rows, _ = reference.reserved_split(
        n, selector.get("reserve_test_fraction", 0.1),
        selector.get("seed", 42))
    cap = selector.get("max_training_sample")
    if cap is not None and len(rows) > int(cap):
        rows = rows[np.sort(np.random.RandomState(
            int(selector.get("seed", 42))).choice(
                len(rows), int(cap), replace=False))]
    return rows


#: the reference's fit is taken only at its optimum: the largest entry of
#: the objective's gradient in standardised units. On the cell's million
#: rows a fit that ran to its end reads 2e-16, one stopped two Newton steps
#: short 1.1e-8 (2.3e-7 off in the coefficients), three short 3.0e-5 (numpy
#: on the sandbox, PR 34, seed 3400000105)
GRADIENT_LIMIT = 1e-11


def _gradient(X: np.ndarray, y: np.ndarray, reg: float, fit: dict,
              block: int = 65536) -> float:
    """Largest entry of the gradient of ``reference.fit_logistic``'s
    objective at ``fit``, over the standardised coefficients and the
    intercept, from the raw matrix in float64: ``fit_logistic`` ends
    silently at its iteration limit or at its shortest step."""
    n, d = X.shape
    coef, std = np.asarray(fit["coef"]), np.asarray(fit["std"])
    live = std > 0
    sums, xr, r_sum = np.zeros(d), np.zeros(d), 0.0
    for lo in range(0, n, block):
        Xb = X[lo:lo + block].astype(np.float64)
        r = 1.0 / (1.0 + np.exp(-(Xb @ coef + fit["bias"]))) - y[lo:lo + block]
        sums += Xb.sum(axis=0)
        xr += Xb.T @ r
        r_sum += float(r.sum())
    g = np.where(live, (xr - sums / n * r_sum) / n / np.where(live, std, 1.0)
                 + float(reg) * coef * std, 0.0)
    return float(max(np.abs(g).max(), abs(r_sum / n)))


class Loop(train_closed_loop.Loop):
    def __init__(self, ctx: Context):
        from transmogrifai_tpu.parallel import sharded
        _needs(sharded, ("take_rows", "place_rows", "pad_rows_sharded"))
        super().__init__(ctx)
        import jax
        from transmogrifai_tpu.parallel import MeshSpec, make_mesh
        self.chips = int(ctx.cell.chips)
        self.devices = jax.local_devices()[:self.chips]
        self.mesh = make_mesh(MeshSpec(**self.traffic["mesh"]),
                              devices=self.devices)

    def prepare_op(self) -> None:
        super().prepare_op()
        self.built.workflow.with_mesh(self.mesh)

    # -- after the window -----------------------------------------------------
    def _score_holdout(self):
        held_table = super()._score_holdout()
        self._reference_training()
        return held_table

    def _reference_training(self) -> None:
        """What ``common.compare_training`` would compute first, on the rows
        the selector fits on: ``reference.fit_logistic`` of the sweep's best
        L2 logistic point, from the reference's own start."""
        point = common._lr_point(self.reports[-1])
        if point is None or getattr(self, "reference_fit",
                                    (None,))[0] == point[0]:
            return
        reg = point[0]
        _, names, held, hold_gen, _ = self.compared
        gen = self.train_gen
        rows = fitted_rows(gen.rows, self.config["workflow"]["selector"])
        slots = (workflows.slots_of(held[names[0]]),
                 workflows.slots_of(held[names[1]]))
        X = reference.feature_matrix(
            {k: v[rows] for k, v in gen.columns.items()}, gen.types, *slots)
        y = gen.label[rows]
        X_hold = reference.feature_matrix(hold_gen.columns, hold_gen.types,
                                          *slots)
        fit = reference.fit_logistic(X, y, reg)
        grad = _gradient(X, y, reg, fit)
        self.ctx.log(f"reference: logistic regParam {reg} on {len(rows)} "
                     f"rows, {fit['iterations']} Newton steps from its own "
                     f"start, largest gradient entry {grad!r}")
        if not grad <= GRADIENT_LIMIT:
            raise RuntimeError(
                f"the reference's fit is not at its optimum (gradient "
                f"{grad!r} > {GRADIENT_LIMIT}): nothing to compare with")
        self.reference_fit = (reg, X, y, X_hold, fit)
        self.reference_cv = None

    def mesh_checks(self) -> List[Check]:
        """What the last train of the window left behind about its
        placement, and the process's counters."""
        import jax
        from transmogrifai_tpu.observability import metrics as obs_metrics
        validator = self.built.selector.validator
        sharding = getattr(validator, "last_sweep_sharding", None)
        shards = getattr(validator, "last_sweep_shards", None) or []
        downgrades = sum(obs_metrics.registry().snapshot().get(
            DOWNGRADES, {}).values())
        n_data = int(self.mesh.shape["data"])
        spec = tuple(getattr(sharding, "spec", ()))
        on = set(getattr(sharding, "device_set", ()))
        shapes = sorted({tuple(shape) for _, shape in shards})
        rows = sum(shape[0] for _, shape in shards)
        kept = float(len(fitted_rows(
            self.train_gen.rows, self.config["workflow"]["selector"])))
        stats = [d.memory_stats() or {} for d in self.devices]
        peaks = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
        # a TPU that reports no memory fails the run; a CPU (tests) has none
        peak_floor = 1.0 if jax.default_backend() == "tpu" else None
        shard_bytes = (shapes[0][0] * shapes[0][1] * 4
                       if len(shapes) == 1 and len(shapes[0]) == 2 else 0)
        self.ctx.log(f"mesh {dict(self.mesh.shape)} over "
                     f"{[str(d) for d in self.devices]}: sweep table "
                     f"{sharding}, shards {shapes}, peak bytes {peaks}, "
                     f"one shard {shard_bytes}")
        return [
            Check("chips_used", float(len(on)), float(self.chips), "min"),
            Check("chips_outside_the_cell", float(len(on - set(self.devices))),
                  0.0),
            Check("mesh_downgrades", float(downgrades), 0.0),
            Check("sweep_table_sharded_over_data",
                  float(spec[:1] == ("data",)
                        and all(s is None for s in spec[1:])
                        and not getattr(sharding, "is_fully_replicated",
                                        True)), 1.0, "min"),
            Check("sweep_table_shards", float(len(shards)),
                  float(self.chips), "min"),
            Check("sweep_table_shard_shapes", float(len(shapes)), 1.0),
            # the shards together are the rows kept, padded to the sweep's
            # row bucket (a fifth at most): neither fewer nor a copy a chip
            Check("sweep_table_rows_over_rows_kept",
                  rows / max(kept, 1.0), 1.0, "min"),
            Check("sweep_table_rows_over_padded_rows_kept",
                  rows / max(kept, 1.0), 1.25),
            Check("sweep_table_rows_divide",
                  float(rows % n_data) if rows else 1.0, 0.0),
            Check("smallest_chip_peak_over_one_shard",
                  (min(peaks) / shard_bytes) if shard_bytes else 0.0,
                  peak_floor, "min"),
        ]

    def check(self) -> List[Check]:
        return self.mesh_checks() + super().check()
