"""The kind ``train_multiclass_closed_loop`` at test size on the CPU, from
files added to a temporary copy of the benchmark: a sound run is
``correct`` and prints every number beside its limit; each control (the
bfloat16 reference, the 200-step Adam refit, the program's own sweep path)
and each broken timed path (a train on half the rows, a sweep whose fits
score the wrong way) comes out not correct on the number meant for it. And
what PR 26 adds to the manifest: the operation count, the peak table and
the roofline reader."""
import collections
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness, opcounts, readers, roofline, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXT = os.path.join(HERE, "data", "extension_multiclass")
CELL = "train-tiny-multiclass"
REAL = "train-kddcup99"
#: the cell's own metrics, and the shared readers that list it
MINE = ("mc_forest_config_chunks", "mc_softmax_roofline")
SHARED = ("sweep_linear_s", "sweep_forest_s", "refit_fit_s", "refit_eval_s",
          "selector_prepare_s")


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
        "name": "tiny-multiclass", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-multiclass.json", "why": "tests"})
    m["workloads"].append(
        {"name": CELL, "config": "tiny-multiclass",
         "traffic": "train_multiclass_tiny_closed_loop", "chips": 1,
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
                 "quarantined_fits", "model_fault_sections", "classes_kept",
                 "feature_vector_max_abs_diff", "prob_max_abs_diff",
                 "weighted_f1", "refit_coef_max_abs_diff",
                 "refit_prob_max_abs_diff", "cv_metric_abs_diff",
                 "planned_vs_eager_max_abs_diff", "fault_kinds_counted"):
        assert any(ln.startswith(f"check {name}:") for ln in lines), name
    assert any(ln.startswith("reference: softmax regParam") for ln in lines)


def test_a_traced_run_reads_the_span_metrics_this_pr_adds(extended):
    root, m = extended
    cell, res, lines = _run(root, m, trace=True)
    assert res["correct"], lines
    got = res["metrics"]
    # spans and counters read on the CPU; the device readers (the two sweep
    # programs' seconds, the roofline) find no device plane and say nothing
    for name in ("refit_fit_s", "refit_eval_s", "selector_prepare_s"):
        assert got[name]["value"] > 0
    assert not {"sweep_linear_s", "mc_softmax_roofline"} & set(got)
    assert "mc_forest_config_chunks" not in got      # no forest in this grid


def _half_the_rows(monkeypatch):
    from benchmark import workflows
    from benchmark.kinds import train_multiclass_closed_loop as kind

    def broken(self):
        half = self.table.take(np.arange(self.table.num_rows // 2))
        self.built = workflows.build_workflow(self.config, half)

    monkeypatch.setattr(kind.Loop, "prepare_op", broken)


def _sweep_scores_the_wrong_way(monkeypatch):
    """A sweep whose fits are wrong while the refit is sound: every
    candidate's coefficients change sign, so the folds are scored with the
    classes the wrong way round."""
    from transmogrifai_tpu.impl.tuning import validators
    from transmogrifai_tpu.models.linear import LogisticRegressionFamily
    real = LogisticRegressionFamily.sweep_fit_batch
    monkeypatch.setattr(validators, "_FUSED_CACHE",
                        collections.OrderedDict())

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        return {"W": -out["W"], "b": -out["b"]}

    monkeypatch.setattr(LogisticRegressionFamily, "sweep_fit_batch", broken)


def _refit_by_adam(monkeypatch):
    """The schedule the solver replaced, in the refit's place: the winner's
    parameters are the reference's 200 Adam steps on the rows it fitted."""
    from benchmark import reference_multiclass as ref
    from benchmark import workflows
    from benchmark.kinds import train_multiclass_closed_loop as kind
    real_op = kind.Loop.op

    def broken(self):
        real_op(self)
        fitted = workflows.selected_model(self.model).fitted
        rows, _ = ref.reserved_split(self.train_gen.rows, 0.1, 42)
        held = self.model.score(table=self.table.take(rows))
        X = np.asarray(held[self.built.checked.name].values, np.float32)
        low = ref.adam_softmax(X, self.train_gen.label[rows].astype(int),
                               float(fitted.hyper["regParam"]), self.classes)
        fitted.params = {"W": low["W"].astype(np.float32),
                         "b": low["b"].astype(np.float32)}

    monkeypatch.setattr(kind.Loop, "op", broken)


def _refit_in_lower_precision(monkeypatch):
    from transmogrifai_tpu.models.linear import LogisticRegressionFamily
    monkeypatch.setattr(LogisticRegressionFamily, "fit_batch",
                        LogisticRegressionFamily.sweep_fit_batch)


@pytest.mark.parametrize("plant, fails", [
    (_half_the_rows, ("refit_coef_max_abs_diff", "refit_prob_max_abs_diff")),
    (_refit_by_adam, ("refit_coef_max_abs_diff", "refit_prob_max_abs_diff")),
    (_refit_in_lower_precision, ("refit_coef_max_abs_diff",
                                 "refit_prob_max_abs_diff")),
    (_sweep_scores_the_wrong_way, ("cv_metric_abs_diff",)),
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
    assert set(controls) == {"bf16 reference", "adam 200 x 0.1",
                             "program's sweep path"}
    for name, checks in controls.items():
        low = {c.name: c for c in checks}
        for number in ("refit_coef_max_abs_diff", "refit_prob_max_abs_diff"):
            assert not low[number].ok, (name, low[number].line())
            assert low[number].value > 10 * sound[number].value
    bf16 = {c.name: c for c in controls["bf16 reference"]}
    assert not bf16["prob_max_abs_diff"].ok
    assert not bf16["feature_vector_max_abs_diff"].ok


# -- what the manifest gains ---------------------------------------------------

def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = harness.load_manifest(ROOT)
    cell = harness.load_cell(ROOT, m, REAL)
    assert cell.chips == 1 and cell.config["problem"] == "multiclass"
    assert cell.traffic["kind"] == "train_multiclass_closed_loop"
    assert cell.traffic["min_ops"] == 6 and cell.traffic["traced_ops"] == 1
    airline = harness.load_cell(ROOT, m, "train-airline").traffic
    assert cell.traffic["process_env"] == airline["process_env"]
    assert cell.config["workflow"]["expected_fits"] == 72
    specs = {s["name"]: s for s in cell.per_layer}
    # its own two are its own; the readers it shares list it among others
    assert {n for n in specs if n.startswith("mc_")} == set(MINE)
    assert set(specs) >= set(SHARED)
    for name in MINE + SHARED:
        spec = specs[name]
        assert (spec["workloads"] == [REAL] if name in MINE
                else REAL in spec["workloads"])
        assert spec["moves"] == "train_s" and len(spec["what"]) > 20
        assert readers.read_metric(spec, readers.Readings()) is None
    assert specs["mc_softmax_roofline"]["unit"] == "%"
    # the many-class selector lists no boosted family: nothing to read
    assert "sweep_gbt_s" not in specs
    # in this cell the linear families' program IS the softmax solver's
    assert specs["mc_softmax_roofline"]["read"]["attrs"]["family"] in \
        specs["sweep_linear_s"]["read"]["attrs"]["family"].split("|")


def test_the_operation_count_is_the_spans_own():
    attrs = {"rows": 900000, "features": 76, "lanes": 18, "classes": 23,
             "contractions": 185}
    assert opcounts.softmax_fit_flops(attrs) == pytest.approx(
        185 * 2 * 900000 * 76 * 18 * 23)
    with pytest.raises(KeyError):
        opcounts.softmax_fit_flops({k: v for k, v in attrs.items()
                                    if k != "contractions"})
    assert roofline.device_peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError):
        roofline.device_peak("cpu", "bf16_flops")


def test_the_roofline_reader_divides_span_flops_by_device_seconds():
    """Two family programs of 2 ms and 4 ms; the logistic one is first."""
    ms = 1e6
    events = [tracered.Event("/device:TPU:0", "XLA Modules", "jit_prog(1)",
                             10 * ms, 2 * ms),
              tracered.Event("/device:TPU:0", "XLA Modules", "jit_prog(2)",
                             13 * ms, 4 * ms),
              tracered.Event("/device:TPU:0", "XLA Ops", "fusion.1",
                             10 * ms, 2 * ms)]
    attrs = {"family": "OpLogisticRegression", "order": 0, "programs": 1,
             "rows": 1000, "features": 10, "lanes": 6, "classes": 5,
             "contractions": 100}
    spans = [SimpleNamespace(name="sweep.family", ts_ns=int(9 * ms),
                             dur_ns=int(ms), attrs=attrs),
             SimpleNamespace(name="sweep.family", ts_ns=int(11 * ms),
                             dur_ns=int(ms),
                             attrs={"family": "OpRandomForestClassifier",
                                    "order": 1, "programs": 1})]
    r = readers.Readings(ops=[(0, int(20 * ms))], traced=[(0, int(20 * ms))],
                         spans=spans, epoch_ns=0,
                         trace=tracered.Trace(events, (0.0, 0.0)))
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", "mc_softmax_roofline.json")))
    flops = 100 * 2 * 1000 * 10 * 30
    got = roofline.read(spec["read"], r, device_kind="TPU v5 lite")
    assert got == pytest.approx(100 * flops / 2e-3 / 197e12)
    # a span without the solver's count (the parent): nothing to read
    del attrs["contractions"]
    assert roofline.read(spec["read"], r, device_kind="TPU v5 lite") is None
    assert readers.KINDS["span_flops_roofline"] is roofline.read
