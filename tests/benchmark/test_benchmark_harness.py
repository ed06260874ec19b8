"""The harness at a tiny size on the CPU, through its internal functions:
"data only" held by a test (a configuration, two traffic mixes, two
per-layer metrics, a reader kind and two cells added as new files to a
temporary copy), runs with the timed path broken underneath come out not
correct, the lower-precision controls fail the limits, and ``run.py``
itself refuses to run without a TPU.
"""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import datagen, harness, reference
from benchmark.kinds import common

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXT = os.path.join(HERE, "data", "extension")


@pytest.fixture()
def extended(tmp_path):
    """A copy of BENCHMARK.json and benchmark/ with the extension's files
    ADDED (none of the copy's own files is touched) and one entry each."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in _files(root)}
    for sub in ("configs", "traffic", "layer_metrics", "reader_kinds"):
        os.makedirs(os.path.join(root, "benchmark", sub), exist_ok=True)
        for name in os.listdir(os.path.join(EXT, sub)):
            dst = os.path.join(root, "benchmark", sub, name)
            assert not os.path.exists(dst)
            shutil.copy(os.path.join(EXT, sub, name), dst)
    m = json.load(open(os.path.join(root, "BENCHMARK.json")))
    m["configs"].append({
        "name": "tiny-mixed", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-mixed.json", "why": "tests"})
    m["workloads"] += [
        {"name": "train-tiny", "config": "tiny-mixed",
         "traffic": "train_tiny_closed_loop", "chips": 1, "why": "tests"},
        {"name": "score-tiny", "config": "tiny-mixed",
         "traffic": "score_tiny_closed_loop", "chips": 1, "why": "tests"}]
    m["per_layer"] += [
        {"name": "tiny_score_span_s", "unit": "s", "better": "lower",
         "source": "program_span", "layer": "scoring plan",
         "moves": "score_rows_per_s", "workloads": ["score-tiny"]},
        {"name": "tiny_score_spans", "unit": "count", "better": "lower",
         "source": "program_span", "layer": "scoring plan",
         "moves": "score_rows_per_s", "workloads": ["score-tiny"]}]
    for e in m["end_to_end"]:
        if e["name"] == "train_s":
            e["workloads"].append("train-tiny")
        if e["name"] == "score_rows_per_s":
            e["workloads"].append("score-tiny")
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
    for p, content in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == content, f"{p} was edited"
    return root, m


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]


def _run(root, manifest, name, trace, seed=2 ** 31 + 11, seconds=0.5):
    lines = []
    cell = harness.load_cell(root, manifest, name)
    result = harness.run_cell(cell, seed, seconds, trace,
                              time.perf_counter(), log=lines.append)
    return cell, result, lines


def test_new_cells_run_from_added_files_alone(extended):
    root, m = extended
    cell, res, lines = _run(root, m, "train-tiny", trace=False)
    assert res["correct"], lines
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_s", "setup_s"}
    assert res["metrics"]["train_s"]["value"] > 0
    assert res["metrics"]["train_s"]["unit"] == "s"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    # every number compared is printed beside its limit
    for name in ("compiles_in_window", "fits", "feature_vector_max_abs_diff",
                 "score_max_abs_diff", "auroc", "refit_coef_max_abs_diff",
                 "refit_score_max_abs_diff", "cv_metric_abs_diff",
                 "planned_vs_eager_max_abs_diff", "fault_kinds_counted"):
        assert any(ln.startswith(f"check {name}:") for ln in lines), name


def test_a_traced_run_reports_the_cells_layer_metrics(extended):
    root, m = extended
    cell, res, lines = _run(root, m, "score-tiny", trace=True)
    assert res["correct"], lines
    got = set(res["metrics"])
    # span, memory-free and set-up readers find something on the CPU; the
    # device readers find no device plane there and leave their metrics out
    assert "tiny_score_span_s" in got
    # ... and so does the reader kind that came as a file of its own
    assert res["metrics"]["tiny_score_spans"]["value"] >= 1
    assert {"setup_compile_s", "setup_trace_lower_s",
            "setup_cache_misses"} <= got
    assert got <= {s["name"] for s in cell.per_layer}
    assert "score_rows_per_s" not in got and "train_s" not in got


def test_a_broken_timed_path_comes_out_not_correct(extended, monkeypatch):
    """An answer altered where it is produced: the model's parameters are
    nudged after set-up, so the window's scores are not the reference's."""
    root, m = extended
    from benchmark.kinds import score_closed_loop

    real_op = score_closed_loop.Loop.op

    def broken(self):
        real_op(self)
        col = self.last[self.pred_name]
        vals = np.array(col.values)
        vals[::7, 1:] += 0.01
        object.__setattr__(col, "values", vals)
        self.last_values = vals

    monkeypatch.setattr(score_closed_loop.Loop, "op", broken)
    _, res, lines = _run(root, m, "score-tiny", trace=False)
    assert res["correct"] is False
    assert any("score_max_abs_diff" in ln and "FAILED" in ln for ln in lines)


def _nothing_fitted(monkeypatch):
    """A step that leaves its state unchanged: the winner's coefficients
    are zeroed where the train hands them over."""
    from benchmark import workflows
    from benchmark.kinds import train_closed_loop
    real_op = train_closed_loop.Loop.op

    def broken(self):
        real_op(self)
        fitted = workflows.selected_model(self.model).fitted
        fitted.params = {k: np.zeros_like(np.asarray(v))
                         for k, v in fitted.params.items()}

    monkeypatch.setattr(train_closed_loop.Loop, "op", broken)


def _half_the_rows(monkeypatch):
    """A part of the batch left out: the workflow is built over the first
    half of the table."""
    from benchmark import workflows
    from benchmark.kinds import train_closed_loop

    def broken(self):
        half = self.table.take(np.arange(self.table.num_rows // 2))
        self.built = workflows.build_workflow(self.config, half)

    monkeypatch.setattr(train_closed_loop.Loop, "prepare_op", broken)


def _refit_in_lower_precision(monkeypatch):
    """The step that would tempt a later PR: the winner refits through the
    sweep's bfloat16 path."""
    from transmogrifai_tpu.models.linear import LogisticRegressionFamily
    monkeypatch.setattr(LogisticRegressionFamily, "fit_batch",
                        LogisticRegressionFamily.sweep_fit_batch)


def _sweep_scores_the_wrong_way(monkeypatch):
    """A sweep whose fits are wrong while the refit is sound: every
    candidate's coefficients change sign."""
    import collections
    from transmogrifai_tpu.impl.tuning import validators
    from transmogrifai_tpu.models.linear import LogisticRegressionFamily
    real = LogisticRegressionFamily.sweep_fit_batch
    # the sweep's compiled programs are kept by family: start without them
    # and leave none of the broken ones behind
    monkeypatch.setattr(validators, "_FUSED_CACHE",
                        collections.OrderedDict())

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        return {"coef": -out["coef"], "bias": -out["bias"]}

    monkeypatch.setattr(LogisticRegressionFamily, "sweep_fit_batch", broken)


@pytest.mark.parametrize("plant, fails", [
    (_nothing_fitted, ("auroc", "refit_coef_max_abs_diff")),
    (_half_the_rows, ("refit_coef_max_abs_diff",
                      "refit_score_max_abs_diff")),
    (_refit_in_lower_precision, ("refit_coef_max_abs_diff",
                                 "refit_score_max_abs_diff")),
    (_sweep_scores_the_wrong_way, ("cv_metric_abs_diff",)),
], ids=lambda v: getattr(v, "__name__", None))
def test_a_broken_train_comes_out_not_correct(extended, monkeypatch, plant,
                                              fails):
    root, m = extended
    plant(monkeypatch)
    _, res, lines = _run(root, m, "train-tiny", trace=False)
    assert res["correct"] is False
    for name in fails:
        assert any(ln.startswith(f"check {name}:") and "FAILED" in ln
                   for ln in lines), (name, lines)


def test_the_lower_precision_control_fails_the_limit(extended):
    root, m = extended
    cell = harness.load_cell(root, m, "train-tiny")
    for seed in (5, 6, 2 ** 31 + 7):
        ctx = harness.Context(cell, seed, 0.0, False,
                              harness.Monitor().install(), lambda s: None)
        loop = harness.loop_for(cell.traffic["kind"])(ctx)
        loop.setup()
        loop.prepare_op()
        loop.op()
        sound = {c.name: c for c in loop.check()}
        assert sound["score_max_abs_diff"].ok
        assert sound["feature_vector_max_abs_diff"].ok
        control = {c.name: c for c in common.control_checks(loop)}
        by_program = {c.name: c for c in loop.program_control()}
        assert not control["score_max_abs_diff"].ok
        assert control["score_max_abs_diff"].value > \
            10 * sound["score_max_abs_diff"].value
        for name in ("refit_coef_max_abs_diff", "refit_score_max_abs_diff"):
            assert sound[name].ok
            for low in (control, by_program):
                assert not low[name].ok
                assert low[name].value > 10 * sound[name].value
        assert sound["cv_metric_abs_diff"].ok


def test_seeds_draw_rows_only():
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "airline-1m.json")))
    a = datagen.generate(cfg, 7, 50000)
    b = datagen.generate(cfg, 7, 50000)
    c = datagen.generate(cfg, 2 ** 31 + 99, 50000)
    for k in a.columns:
        assert np.array_equal(a.columns[k], b.columns[k])
    assert np.array_equal(a.label, b.label)
    assert not np.array_equal(a.label, c.label)
    for col in cfg["columns"]:
        if col["type"] == "PickList":
            # the same label rule and level set whatever the seed
            assert set(a.columns[col["name"]]) <= set(
                datagen.level_names(col))
    assert abs(a.label.mean() - c.label.mean()) < 0.02
    assert 0.15 < a.label.mean() < 0.25


def test_bf16_rounding_is_round_to_nearest_even():
    x = np.array([1.0, 1.00390625, 1.01171875, 3.14159, np.inf, -2.5e-3],
                 dtype=np.float32)
    got = reference.to_bf16(x)
    import jax.numpy as jnp
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(got, want)


def test_the_entry_refuses_without_a_tpu_and_prints_nothing(capsys):
    """``run.py`` hands its arguments to ``harness.main``: on this CPU it
    returns non-zero before any set-up, with nothing on stdout."""
    rc = harness.main(["--workload", "score-higgs", "--seed", "1",
                       "--seconds", "1", "--trace", "0"],
                      time.perf_counter(), ROOT)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "TPU" in out.err


def test_run_py_sets_the_traffics_process_env_before_anything_else(
        monkeypatch):
    from benchmark import run

    class Execed(Exception):
        pass

    def fake_exec(exe, argv):
        raise Execed(argv)

    want = run.process_env(["--workload", "train-airline"])
    assert set(want) == {"MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_"}
    assert want == run.process_env(["--workload=score-higgs"])
    assert run.process_env(["--seed", "1"]) == {}
    assert run.process_env(["--workload", "no-such-cell"]) == {}
    # a copy of the environment, so that nothing of this outlives the test
    monkeypatch.setattr(os, "environ", dict(os.environ))
    for k in want:
        os.environ.pop(k, None)
    monkeypatch.setattr(os, "execv", fake_exec)
    monkeypatch.setattr("sys.argv", ["benchmark/run.py", "--workload",
                                     "train-airline", "--seed", "1"])
    with pytest.raises(Execed):
        run.main()
    assert all(os.environ[k] == v for k, v in want.items())
    assert "TG_BENCH_T0" in os.environ


def test_the_diagnostic_tool_prints_ops_checks_and_controls(extended,
                                                            monkeypatch,
                                                            capsys):
    import jax
    from benchmark.tools import repeat
    root, m = extended
    argv = ["--workload", "train-tiny", "--seeds", "21", "--ops", "2",
            "--control", "1"]
    assert repeat.main(argv, root=root) == 2          # this is no TPU
    assert capsys.readouterr().out == ""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(harness, "configure_jax", lambda root: None)
    lines = []
    assert repeat.main(argv, root=root, log=lines.append) == 0
    text = "\n".join(lines)
    assert text.count("  op ") == 2 and "wall" in text and "built 0" in text
    assert "check refit_score_max_abs_diff" in text
    assert "control(bf16 reference) check score_max_abs_diff" in text
    assert "control(program's sweep path) check refit_coef_max_abs_diff" \
        in text
