"""The kind ``train_regression_closed_loop`` at test size on the CPU, from
files added to a temporary copy of the benchmark: a sound run is ``correct``
and prints every number beside its limit; each control (the bfloat16
reference, the 60 ISTA steps and the IRLS from zero that the program had
before PR 30) and each broken timed path (a train on half the rows, a refit
by the old ISTA schedule, poisson lanes that fit a constant, a sweep that
scores with the wrong sign) comes out not correct on the number meant for
it. And what PR 30 adds to the manifest: the byte count and the bandwidth
roofline reader."""
import collections
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import bytecounts, harness, readers, roofline_bytes, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXT = os.path.join(HERE, "data", "extension_regression")
CELL = "train-tiny-regression"
REAL = "train-nyctaxi"
#: the cell's own metrics, and the shared readers PR 30 first read here
MINE = ("rg_fe_date_s", "rg_gram_roofline")
NEW = ("sweep_linear_s", "sweep_forest_s", "sweep_gbt_s", "refit_fit_s",
       "refit_eval_s", "selector_prepare_s") + MINE


@pytest.fixture()
def extended(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic"):
        for name in os.listdir(os.path.join(EXT, sub)):
            dst = os.path.join(root, "benchmark", sub, name)
            assert not os.path.exists(dst)
            shutil.copy(os.path.join(EXT, sub, name), dst)
    m = json.load(open(os.path.join(root, "BENCHMARK.json")))
    m["configs"].append({
        "name": "tiny-regression", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-regression.json", "why": "tests"})
    m["workloads"].append(
        {"name": CELL, "config": "tiny-regression",
         "traffic": "train_regression_tiny_closed_loop", "chips": 1,
         "why": "tests"})
    for e in m["end_to_end"] + m["per_layer"]:
        if REAL in e.get("workloads", []):
            e["workloads"].append(CELL)
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root, m


def _run(root, manifest, trace=False, seed=2 ** 31 + 11):
    lines = []
    cell = harness.load_cell(root, manifest, CELL)
    result = harness.run_cell(cell, seed, 0.5, trace, time.perf_counter(),
                              log=lines.append)
    return cell, result, lines


def _failed(lines, name):
    return any(ln.startswith(f"check {name}:") and "FAILED" in ln
               for ln in lines)


def test_a_sound_run_is_correct_and_prints_every_number(extended):
    root, m = extended
    _, res, lines = _run(root, m)
    assert res["correct"], lines
    assert set(res["metrics"]) == {"train_s", "setup_s"}
    for name in ("compiles_in_window", "fits", "fits_finite",
                 "quarantined_fits", "model_fault_sections",
                 "feature_vector_max_abs_diff", "pred_max_rel_diff",
                 "preds_finite", "r2", "refit_coef_max_abs_diff",
                 "refit_pred_max_rel_diff", "cv_metric_rel_diff",
                 "poisson_lanes_fitted", "planned_vs_eager_max_abs_diff",
                 "fault_kinds_counted"):
        assert any(ln.startswith(f"check {name}:") for ln in lines), name
    assert any(ln.startswith("reference: OpLinearRegression") for ln in lines)


def test_a_traced_run_reads_the_span_metrics_this_pr_adds(extended):
    root, m = extended
    cell, res, lines = _run(root, m, trace=True)
    got = res["metrics"]
    # spans read on the CPU; the device readers (the family programs'
    # seconds, the roofline) find no device plane and say nothing
    for name in ("refit_fit_s", "refit_eval_s", "selector_prepare_s",
                 "rg_fe_date_s"):
        assert got[name]["value"] > 0
    assert not {"sweep_linear_s", "sweep_forest_s", "sweep_gbt_s",
                "rg_gram_roofline"} & set(got)
    assert {s["name"] for s in cell.per_layer} >= set(NEW)


def _half_the_rows(monkeypatch):
    from benchmark import workflows
    from benchmark.kinds import train_regression_closed_loop as kind

    def broken(self):
        half = self.table.take(np.arange(self.table.num_rows // 2))
        self.built = workflows.build_workflow(self.config, half)

    monkeypatch.setattr(kind.Loop, "prepare_op", broken)


def _winner_fitted_by(make_params):
    """A schedule the program had, in the refit's place: the winner's
    parameters are the reference's control fit on the rows it fitted."""
    def plant(monkeypatch):
        from benchmark import reference_regression as ref
        from benchmark import workflows
        from benchmark.kinds import train_regression_closed_loop as kind
        real_op = kind.Loop.op

        def broken(self):
            real_op(self)
            fitted = workflows.selected_model(self.model).fitted
            rows, _ = ref.reserved_split(self.train_gen.rows, 0.1, 42)
            held = self.model.score(table=self.table.take(rows))
            X = np.asarray(held[self.built.checked.name].values, np.float32)
            low = make_params(ref, X, self.train_gen.label[rows],
                              fitted.hyper)
            fitted.params = dict(
                fitted.params, coef=low["coef"].astype(np.float32),
                bias=np.float32(low["bias"]))

        monkeypatch.setattr(kind.Loop, "op", broken)
    return plant


_refit_in_bfloat16 = _winner_fitted_by(
    lambda ref, X, y, h: ref.fit_linear(X, y, h["regParam"],
                                        h["elasticNetParam"], "bf16"))
_refit_in_bfloat16.__name__ = "_refit_in_bfloat16"


def _l1_refit_by_ista(monkeypatch):
    """The sweep's points with an L1 term alone, refitted by the 60 ISTA
    steps at 1 / trace that the program ran before PR 30."""
    from benchmark.kinds import train_regression_closed_loop as kind
    real_init = kind.Loop.__init__

    def only_l1(self, ctx):
        real_init(self, ctx)
        models = self.config["workflow"]["selector"]["models"]
        models[0][1] = [g for g in models[0][1] if g["elasticNetParam"]]
        self.config["workflow"]["expected_fits"] = 12

    monkeypatch.setattr(kind.Loop, "__init__", only_l1)
    _winner_fitted_by(lambda ref, X, y, h: ref.ista_linear(
        X, y, h["regParam"], h["elasticNetParam"]))(monkeypatch)


def _poisson_from_zero(monkeypatch):
    """IRLS from theta = 0 in the generalised-linear sweep's place: the
    poisson lanes keep the zero vector and predict exp(0)."""
    import jax.numpy as jnp

    from transmogrifai_tpu.impl.tuning import validators
    from transmogrifai_tpu.models.glm import (
        GeneralizedLinearRegressionFamily as Glm)
    real = Glm.fit_batch
    monkeypatch.setattr(validators, "_FUSED_CACHE",
                        collections.OrderedDict())

    def broken(self, X, y, weights, grid, num_classes):
        out = real(self, X, y, weights, grid, num_classes)
        dead = (out["family"] > 0)
        return {"coef": jnp.where(dead[:, None], 0.0, out["coef"]),
                "bias": jnp.where(dead, 0.0, out["bias"]),
                "family": out["family"]}

    monkeypatch.setattr(Glm, "fit_batch", broken)


def _sweep_scores_the_wrong_way(monkeypatch):
    """A sweep whose fits are wrong while the refit is sound: every linear
    and generalised-linear candidate's coefficients change sign."""
    from transmogrifai_tpu.impl.tuning import validators
    from transmogrifai_tpu.models.api import ModelFamily
    from transmogrifai_tpu.models.glm import (
        GeneralizedLinearRegressionFamily as Glm)
    from transmogrifai_tpu.models.linear import LinearRegressionFamily as Lin
    monkeypatch.setattr(validators, "_FUSED_CACHE",
                        collections.OrderedDict())

    def broken(self, *a, **kw):
        out = ModelFamily.sweep_fit_batch(self, *a, **kw)
        return dict(out, coef=-out["coef"])

    for family in (Lin, Glm):
        monkeypatch.setattr(family, "sweep_fit_batch", broken,
                            raising=False)


@pytest.mark.parametrize("plant, fails", [
    (_half_the_rows, ("refit_coef_max_abs_diff", "refit_pred_max_rel_diff")),
    (_refit_in_bfloat16, ("refit_coef_max_abs_diff",
                          "refit_pred_max_rel_diff")),
    (_l1_refit_by_ista, ("refit_coef_max_abs_diff",
                         "refit_pred_max_rel_diff")),
    (_poisson_from_zero, ("poisson_lanes_fitted",)),
    (_sweep_scores_the_wrong_way, ("cv_metric_rel_diff",)),
], ids=lambda v: getattr(v, "__name__", None))
def test_a_broken_train_comes_out_not_correct(extended, monkeypatch, plant,
                                              fails):
    root, m = extended
    plant(monkeypatch)
    _, res, lines = _run(root, m)
    assert res["correct"] is False
    for name in fails:
        assert _failed(lines, name), (name, lines)


def test_the_controls_fail_the_limits_a_sound_run_keeps(extended):
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    ctx = harness.Context(cell, 5, 0.0, False, harness.Monitor().install(),
                          lambda s: None)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    loop.setup()
    loop.prepare_op()
    loop.op()
    sound = {c.name: c for c in loop.check()}
    assert all(c.ok for c in sound.values()), [c.line() for c in
                                               sound.values() if not c.ok]
    controls = loop.controls()
    assert set(controls) == {"bf16 reference", "ista 60 x 1/trace",
                             "irls from theta 0"}
    for name in ("bf16 reference", "ista 60 x 1/trace"):
        low = {c.name: c for c in controls[name]}
        for number in ("refit_coef_max_abs_diff", "refit_pred_max_rel_diff"):
            assert not low[number].ok, (name, low[number].line())
            assert low[number].value > 10 * sound[number].value
    bf16 = {c.name: c for c in controls["bf16 reference"]}
    assert not bf16["pred_max_rel_diff"].ok
    assert not bf16["feature_vector_max_abs_diff"].ok
    (lanes,) = controls["irls from theta 0"]
    assert lanes.name == "poisson_lanes_fitted" and lanes.value == 0
    assert not lanes.ok and sound["poisson_lanes_fitted"].value == 2


# -- what the manifest gains ---------------------------------------------------

def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = harness.load_manifest(ROOT)
    cell = harness.load_cell(ROOT, m, REAL)
    assert cell.chips == 1 and cell.config["problem"] == "regression"
    assert cell.traffic["kind"] == "train_regression_closed_loop"
    assert cell.traffic["min_ops"] == 6 and cell.traffic["traced_ops"] == 1
    assert cell.traffic["collect_garbage_between_ops"] is True
    assert cell.traffic["reports"] == {"train_s": "median_op_seconds"}
    airline = harness.load_cell(ROOT, m, "train-airline").traffic
    assert cell.traffic["process_env"] == airline["process_env"]
    assert {e["name"] for e in cell.end_to_end} == {"train_s", "setup_s"}
    specs = {s["name"]: s for s in cell.per_layer}
    assert {n for n in specs if n.startswith("rg_")} == set(MINE)
    assert set(specs) >= set(NEW)
    for name in NEW:
        spec = specs[name]
        assert REAL in spec["workloads"]
        assert spec["moves"] == "train_s" and len(spec["what"]) > 20
        assert readers.read_metric(spec, readers.Readings()) is None
    assert specs["rg_gram_roofline"]["workloads"] == [REAL]
    assert specs["rg_gram_roofline"]["unit"] == "%"
    # the roofline's seconds are the linear families' programs', which in
    # this cell are the linear and the generalised-linear one
    gram = specs["rg_gram_roofline"]["read"]["seconds"]["attrs"]["family"]
    assert set(gram.split("|")) <= set(
        specs["sweep_linear_s"]["read"]["attrs"]["family"].split("|"))
    assert set(specs) >= {"refit_s", "fe_s", "sanity_s", "sweep_s",
                          "train_device_busy_s", "train_device_idle_pct",
                          "train_hbm_peak_gb", "setup_compile_s"}


def test_the_byte_count_is_the_spans_own():
    attrs = {"rows": 3600000, "features": 30, "gramPasses": 11}
    assert bytecounts.moment_passes_bytes(attrs) == pytest.approx(
        11 * 3600000 * 30 * 4)
    with pytest.raises(KeyError):
        bytecounts.moment_passes_bytes({k: v for k, v in attrs.items()
                                        if k != "gramPasses"})


def test_the_roofline_reader_divides_span_bytes_by_device_seconds():
    """Four family programs; the linear one (2 ms) is first and the
    generalised-linear one (6 ms) last."""
    ms = 1e6
    durs = [2, 4, 3, 6]
    events, spans, t = [], [], 10.0
    fams = ["OpLinearRegression", "OpRandomForestRegressor",
            "OpGBTRegressor", "OpGeneralizedLinearRegression"]
    passes = {"OpLinearRegression": 5, "OpGeneralizedLinearRegression": 11}
    for i, (fam, dur) in enumerate(zip(fams, durs)):
        events.append(tracered.Event("/device:TPU:0", "XLA Modules",
                                     f"jit_prog({i})", t * ms, dur * ms))
        attrs = {"family": fam, "order": i, "programs": 1, "rows": 1000,
                 "features": 10}
        if fam in passes:
            attrs["gramPasses"] = passes[fam]
        spans.append(SimpleNamespace(name="sweep.family",
                                     ts_ns=int((i + 1) * ms), dur_ns=int(ms),
                                     attrs=attrs))
        t += dur + 1
    r = readers.Readings(ops=[(0, int(40 * ms))], traced=[(0, int(40 * ms))],
                         spans=spans, epoch_ns=0,
                         trace=tracered.Trace(events, (0.0, 0.0)))
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "rg_gram_roofline.json")))
    nbytes = (5 + 11) * 1000 * 10 * 4
    got = roofline_bytes.read(spec["read"], r, device_kind="TPU v5 lite")
    assert got == pytest.approx(100 * nbytes / 8e-3 / 819e9)
    linear_s = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "sweep_linear_s.json")))
    assert readers.reader_for("device_op_by_span_order", os.path.join(
        ROOT, "benchmark"))(linear_s["read"], r) == pytest.approx(8e-3)
    # a span without the fit's own count (the parent): nothing to read
    del spans[0].attrs["gramPasses"]
    assert roofline_bytes.read(spec["read"], r,
                               device_kind="TPU v5 lite") is None
    assert readers.KINDS["span_bytes_roofline"] is roofline_bytes.read
