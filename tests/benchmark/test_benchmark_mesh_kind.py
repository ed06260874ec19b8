"""The kind ``train_mesh_closed_loop`` at test size on the CPU's virtual
devices, from files added to a temporary copy of the benchmark: a sound run
is ``correct`` and prints every number beside its limit; a run whose mesh
was downgraded, whose table lies whole on every device, or whose refit went
through the sweep's path comes out not correct on the number meant for it;
the traced run reads the metrics that spans and counters give;
the reader kinds that tell device planes apart, on a made-up four-plane
trace; the reference's fit, taken from its own start and only at its optimum;
and what PR 34 adds to the manifest."""
import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

from benchmark import harness, mesh_readers, readers, tracered
from benchmark.kinds import common

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXT = os.path.join(HERE, "data", "extension_mesh")
CELL = "train-tiny-mesh"
REAL = "train-airline-10m-mesh"
#: the cell's own metrics (what exists only across chips), and the shared
#: readers PR 34 first read here
MINE = ("mesh_collective_s", "mesh_collective_pct", "mesh_busy_skew_pct",
        "mesh_place_s", "mesh_h2d_gb", "mesh_hbm_min_gb", "mesh_downgrades",
        "mesh_take_rows_s", "mesh_take_rows_roofline")
NEW = MINE + ("sweep_linear_s", "sweep_gbt_s", "sweep_forest_s",
              "refit_fit_s", "refit_eval_s", "selector_prepare_s",
              "refit_roofline", "fe_onehot_fit_s", "fe_onehot_transform_s",
              "fe_onehot_encode_s", "fe_onehot_expand_s", "fe_combine_s",
              "train_h2d_gb")
SHARED = ("refit_s", "fe_s", "sanity_s", "sweep_s", "train_device_busy_s",
          "train_device_idle_pct", "train_hbm_peak_gb")


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
        "name": "tiny-mesh", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-mesh.json", "why": "tests"})
    m["workloads"].append(
        {"name": CELL, "config": "tiny-mesh",
         "traffic": "train_mesh_tiny_closed_loop", "chips": 2,
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
    for name in ("compiles_in_window", "chips_used", "chips_outside_the_cell",
                 "mesh_downgrades", "sweep_table_sharded_over_data",
                 "sweep_table_shards", "sweep_table_shard_shapes",
                 "sweep_table_rows_over_rows_kept",
                 "sweep_table_rows_over_padded_rows_kept",
                 "sweep_table_rows_divide",
                 "smallest_chip_peak_over_one_shard", "fits", "fits_finite",
                 "feature_vector_max_abs_diff", "score_max_abs_diff", "auroc",
                 "refit_coef_max_abs_diff", "refit_score_max_abs_diff",
                 "cv_metric_abs_diff", "planned_vs_eager_max_abs_diff",
                 "fault_kinds_counted"):
        assert any(ln.startswith(f"check {name}:") for ln in lines), name
    # the cost model engaged by itself: nothing forces the mesh on
    assert "TG_MESH_FORCE" not in os.environ
    assert any(ln.startswith("mesh {'data': 2, 'model': 1}") for ln in lines)


def _downgraded(monkeypatch):
    """The program's cost model finds the table too small for the mesh and
    runs the sweep on one device."""
    monkeypatch.setenv("TG_MESH_MIN_ROWS_PER_CHIP", str(10 ** 9))


def _table_whole_on_every_device(monkeypatch):
    """A mesh that shares nothing: both devices on the 'model' axis, so
    each holds every row."""
    from benchmark.kinds import train_mesh_closed_loop as kind
    from transmogrifai_tpu.parallel import MeshSpec, make_mesh
    real = kind.Loop.__init__

    def init(self, ctx):
        real(self, ctx)
        self.mesh = make_mesh(MeshSpec(data=1, model=2),
                              devices=self.devices)

    monkeypatch.setattr(kind.Loop, "__init__", init)
    monkeypatch.setenv("TG_MESH_MIN_CONFIGS_PER_CHIP", "0")


def _refit_in_lower_precision(monkeypatch):
    from transmogrifai_tpu.models.linear import LogisticRegressionFamily
    monkeypatch.setattr(LogisticRegressionFamily, "fit_batch",
                        LogisticRegressionFamily.sweep_fit_batch)


@pytest.mark.parametrize("plant, fails", [
    (_downgraded, ("mesh_downgrades", "chips_used")),
    (_table_whole_on_every_device, ("sweep_table_sharded_over_data",
                                    "sweep_table_rows_over_padded_rows_kept")),
    (_refit_in_lower_precision, ("refit_coef_max_abs_diff",
                                 "refit_score_max_abs_diff")),
], ids=lambda v: getattr(v, "__name__", None))
def test_a_broken_mesh_train_comes_out_not_correct(extended, monkeypatch,
                                                   plant, fails):
    root, m = extended
    plant(monkeypatch)
    _, res, lines = _run(root, m)
    assert res["correct"] is False
    for name in fails:
        assert _failed(lines, name), (name, lines)


def test_the_lower_precision_control_fails_the_limits(extended):
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    ctx = harness.Context(cell, 2 ** 31 + 7, 0.0, False,
                          harness.Monitor().install(), lambda s: None)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    loop.setup()
    loop.prepare_op()
    loop.op()
    sound = {c.name: c for c in loop.check()}
    control = {c.name: c for c in common.control_checks(loop)}
    assert sound["score_max_abs_diff"].ok
    assert not control["score_max_abs_diff"].ok
    for name in ("refit_coef_max_abs_diff", "refit_score_max_abs_diff"):
        assert sound[name].ok, (name, sound[name].value)
        assert not control[name].ok
        assert control[name].value > 5 * sound[name].value


def test_a_traced_run_reads_the_span_metrics_this_pr_adds(extended):
    root, m = extended
    cell, res, lines = _run(root, m, trace=True)
    got = res["metrics"]
    for name in ("mesh_place_s", "mesh_h2d_gb", "refit_fit_s",
                 "refit_eval_s", "selector_prepare_s", "fe_onehot_fit_s",
                 "fe_onehot_transform_s", "fe_onehot_encode_s",
                 "fe_onehot_expand_s", "fe_combine_s", "train_h2d_gb"):
        assert got[name]["value"] > 0, name
    # the splits lie inside what they split
    assert got["fe_combine_s"]["value"] < got["fe_s"]["value"]
    assert got["train_h2d_gb"]["value"] >= got["mesh_h2d_gb"]["value"]
    assert got["mesh_downgrades"]["value"] == 0
    # what the host sent as shards: the combined matrix at the least
    rows = cell.config["rows"]
    assert got["mesh_h2d_gb"]["value"] * 1e9 >= rows * 29 * 4
    # no device plane and no memory_stats on the CPU: those say nothing
    assert not {"mesh_collective_s", "mesh_collective_pct",
                "mesh_busy_skew_pct", "mesh_hbm_min_gb",
                "sweep_linear_s", "refit_roofline",
                "mesh_take_rows_s", "mesh_take_rows_roofline"} & set(got)
    assert {s["name"] for s in cell.per_layer} >= set(NEW) | set(SHARED)


def test_a_program_without_the_sharded_placement_is_refused_at_once(
        extended, monkeypatch):
    root, m = extended
    from transmogrifai_tpu.parallel import sharded
    monkeypatch.delattr(sharded, "take_rows")
    cell = harness.load_cell(root, m, CELL)
    ctx = harness.Context(cell, 1, 0.0, False, harness.Monitor().install(),
                          lambda s: None)
    with pytest.raises(SystemExit) as e:
        harness.loop_for(cell.traffic["kind"])(ctx)
    assert "take_rows" in str(e.value) and e.value.code not in (0, None)


# ---------------------------------------------------------------------------
# the reader kinds, on a made-up trace of four device planes
# ---------------------------------------------------------------------------

MS = 1e6


def _four_planes():
    """One traced operation of 100 ms. Every plane runs a 10 ms fusion, an
    all-reduce pair (start 1 ms, done 3 ms) and the refit program; plane 3
    runs 6 ms of fusion more. A row gather's program covers 5 to 35 ms on
    every plane: it starts before its first operation does (it waits for an
    argument) and ends before plane 3's last one."""
    events = []
    for i in range(4):
        plane = f"/device:TPU:{i}"
        ops = [("%fusion.1 = f32[8] fusion(...)", 10.0, 10.0),
               ("%all-reduce-start.2 = f32[8] all-reduce-start(...)", 20.0,
                1.0),
               ("%all-reduce-done.2 = f32[8] all-reduce-done(...)", 22.0,
                3.0)]
        if i == 3:
            ops.append(("%fusion.9 = f32[8] fusion(...)", 30.0, 6.0))
        for name, at, dur in ops:
            events.append(tracered.Event(plane, "XLA Ops", name, at * MS,
                                         dur * MS))
        events.append(tracered.Event(plane, "XLA Modules",
                                     "jit__fit_logreg_batch(77)", 40.0 * MS,
                                     20.0 * MS))
        events.append(tracered.Event(plane, "XLA Modules",
                                     "jit__take_rows(5)", 5.0 * MS,
                                     30.0 * MS))
    take = SimpleNamespace(
        name="mesh.take_rows", ts_ns=int(4 * MS), dur_ns=int(1 * MS),
        attrs={"rows": 4000, "rowsPerChip": 1000, "rowBytes": 40,
               "shards": 4, "steps": 1, "site": "selector.evaluate"})
    span = SimpleNamespace(
        name="selector.refit", ts_ns=int(39 * MS), dur_ns=int(25 * MS),
        attrs={"family": "OpLogisticRegression", "rowsPerChip": 1000,
               "features": 10, "matrixPasses": 205})
    return readers.Readings(
        ops=[(0, int(100 * MS))], traced=[(0, int(100 * MS))],
        spans=[span, take],
        epoch_ns=0, trace=tracered.Trace(events, (0.0, 0.0)))


def _spec(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       name + ".json")))


def test_collective_seconds_are_a_mean_over_the_planes():
    r = _four_planes()
    assert readers.read_metric(_spec("mesh_collective_s"), r) \
        == pytest.approx(4e-3)
    # a reduce-scatter made asynchronous goes by its wrapper's name
    r.trace.events.append(tracered.Event(
        "/device:TPU:0", "XLA Ops",
        "%async-collective-done.3 = f32[8] async-done(...)", 70.0 * MS,
        2.0 * MS))
    r.trace._busy.clear()
    assert readers.read_metric(_spec("mesh_collective_s"), r) \
        == pytest.approx(4.5e-3)
    r.trace.events.pop()
    r.trace._busy.clear()
    # busy: 14 ms on three planes, 20 ms on the fourth
    assert readers.read_metric(_spec("mesh_collective_pct"), r) \
        == pytest.approx(100 * 16.0 / 62.0)


def test_busy_skew_is_the_spread_between_the_planes():
    r = _four_planes()
    assert readers.read_metric(_spec("mesh_busy_skew_pct"), r) \
        == pytest.approx(100 * 6.0 / 20.0)
    one = readers.Readings(
        ops=r.ops, traced=r.traced, epoch_ns=0, trace=tracered.Trace(
            [e for e in r.trace.events if e.plane.endswith(":0")],
            (0.0, 0.0)))
    assert readers.read_metric(_spec("mesh_busy_skew_pct"), one) is None


def test_the_refit_roofline_is_one_chips_bytes_over_one_chips_seconds():
    r = _four_planes()
    spec = _spec("refit_roofline")
    assert spec["unit"] == "%" and "lower bound" in spec["what"]
    nbytes = 1000 * 10 * 4 * 205
    assert mesh_readers.refit_chip_bytes(r.spans[0].attrs) == nbytes
    got = mesh_readers.span_chip_bytes_roofline(spec["read"], r,
                                                device_kind="TPU v5 lite")
    assert got == pytest.approx(100 * nbytes / 20e-3 / 819e9)
    # a linear SVC winner: its own span and program are read the same way
    svc = _four_planes()
    svc.spans[0].attrs["family"] = "OpLinearSVC"
    svc.trace = tracered.Trace(
        [e if not e.name.startswith("jit__fit_logreg") else tracered.Event(
            e.plane, e.line, "jit__fit_svc_batch(78)", e.start_ns, e.dur_ns)
         for e in svc.trace.events], (0.0, 0.0))
    assert mesh_readers.span_chip_bytes_roofline(
        spec["read"], svc, device_kind="TPU v5 lite") == pytest.approx(got)
    # a span without the fit's own count (a winner of a tree family)
    del r.spans[0].attrs["matrixPasses"]
    assert mesh_readers.span_chip_bytes_roofline(
        spec["read"], r, device_kind="TPU v5 lite") is None


def test_a_programs_share_of_busy_is_what_ran_inside_it():
    """``module_busy_sum``: the operations' union inside the program's
    events, not the events' own length (30 ms here, waits and all)."""
    r = _four_planes()
    # 14 ms inside on three planes; 14 + 5 of plane 3's 6 ms more
    assert readers.read_metric(_spec("mesh_take_rows_s"), r) \
        == pytest.approx((3 * 14e-3 + 19e-3) / 4)
    nbytes = 2 * 1000 * 40
    assert mesh_readers.take_chip_bytes(r.spans[1].attrs) == nbytes
    spec = _spec("mesh_take_rows_roofline")
    assert spec["unit"] == "%" and "lower bound" in spec["what"]
    assert mesh_readers.span_chip_bytes_roofline(
        spec["read"], r, device_kind="TPU v5 lite") == pytest.approx(
            100 * nbytes / ((3 * 14e-3 + 19e-3) / 4) / 819e9)
    # a program that no plane ran, a trace without a modules line: nothing
    assert mesh_readers.module_busy_sum({"pattern": "^jit_nothing"}, r) is None
    ops_only = readers.Readings(
        ops=r.ops, traced=r.traced, epoch_ns=0, trace=tracered.Trace(
            [e for e in r.trace.events if e.line == "XLA Ops"], (0.0, 0.0)))
    assert readers.read_metric(_spec("mesh_take_rows_s"), ops_only) is None
    # the parent's program has no such span: the roofline says nothing
    r.spans.pop()
    assert mesh_readers.span_chip_bytes_roofline(
        spec["read"], r, device_kind="TPU v5 lite") is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_finds_nothing_in_an_empty_run(name):
    spec = _spec(name)
    assert (spec["workloads"] == [REAL] if name in MINE
            else REAL in spec["workloads"])
    assert spec["moves"] == "train_s"
    assert len(spec["what"]) > 20
    from benchmark.kinds import train_mesh_closed_loop  # noqa: F401
    assert readers.read_metric(spec, readers.Readings()) is None


def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = harness.load_manifest(ROOT)
    cell = harness.load_cell(ROOT, m, REAL)
    assert cell.chips == 4 and cell.config["name"] == "airline-10m"
    assert cell.config["rows"] == 10_000_000
    assert cell.config["holdout_rows"] == 100_000
    assert cell.config["reduced"] == ["label_rule"]
    assert cell.traffic["kind"] == "train_mesh_closed_loop"
    assert cell.traffic["mesh"] == {"data": 4, "model": 1}
    assert cell.traffic["min_ops"] == 6 and cell.traffic["traced_ops"] == 1
    assert cell.traffic["reports"] == {"train_s": "median_op_seconds"}
    airline = harness.load_cell(ROOT, m, "train-airline")
    assert cell.traffic["process_env"] == airline.traffic["process_env"]
    # the source's schema and label rule, at ten times the rows
    for key in ("columns", "label_rule", "label", "problem"):
        assert cell.config[key] == airline.config[key], key
    mine, theirs = cell.config["workflow"], airline.config["workflow"]
    assert mine["expected_fits"] == theirs["expected_fits"] == 135
    for key in ("validation", "folds", "models", "seed",
                "reserve_test_fraction"):
        assert mine["selector"][key] == theirs["selector"][key], key
    assert {e["name"] for e in cell.end_to_end} == {"train_s", "setup_s"}
    names = {s["name"] for s in cell.per_layer}
    assert names >= set(NEW) | set(SHARED) | {"setup_compile_s"}
    assert {n for n in names if n.startswith("mesh_")} == set(MINE)
    # every limit of the comparison is stated with its reason
    check = cell.config["check"]
    for key in ("score_max_abs_diff", "refit_coef_max_abs_diff",
                "refit_score_max_abs_diff", "cv_metric_abs_diff"):
        assert key in check and key in check["reasons"]


def _logistic_rows(n=4000, d=6, seed=3):
    import numpy as np
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, -1] = 1.5                      # a constant column keeps coefficient 0
    z = X[:, :3] @ np.array([1.0, -2.0, 0.5]) - 0.7
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return X, y


def test_the_gradient_tells_a_fit_at_its_optimum_from_one_short_of_it():
    from benchmark import reference
    from benchmark.kinds import train_mesh_closed_loop as kind
    X, y = _logistic_rows()
    fit = reference.fit_logistic(X, y, 0.01)
    assert kind._gradient(X, y, 0.01, fit) <= kind.GRADIENT_LIMIT
    # blocks of rows change nothing but the order of the sums
    assert kind._gradient(X, y, 0.01, fit, block=700) == pytest.approx(
        kind._gradient(X, y, 0.01, fit), abs=1e-15)
    short = reference.fit_logistic(X, y, 0.01, max_iter=2)
    assert kind._gradient(X, y, 0.01, short) > 100 * kind.GRADIENT_LIMIT
    # the same objective: another penalty's optimum is not this one's
    assert kind._gradient(X, y, 0.1, fit) > 100 * kind.GRADIENT_LIMIT


@pytest.mark.parametrize("max_iter, raises", [(None, False), (2, True)])
def test_the_reference_fits_from_its_own_start_and_only_to_its_optimum(
        extended, monkeypatch, max_iter, raises):
    """The kind hands ``compare_training`` a fit of ``reference.py`` that
    started where the reference starts (never at the program's answer), and
    refuses one that stopped short of the optimum."""
    from benchmark import reference
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    ctx = harness.Context(cell, 2 ** 31 + 9, 0.0, False,
                          harness.Monitor().install(), lambda s: None)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    loop.setup()
    loop.prepare_op()
    loop.op()
    calls = []
    real = reference.fit_logistic

    def fit(X, y, reg, *args, **kw):
        calls.append((X.shape[0], args, dict(kw)))
        if max_iter is not None:
            kw["max_iter"] = max_iter
        return real(X, y, reg, *args, **kw)

    monkeypatch.setattr(reference, "fit_logistic", fit)
    if raises:
        with pytest.raises(RuntimeError, match="not at its optimum"):
            loop._score_holdout()
        return
    loop._score_holdout()
    from benchmark.kinds.train_mesh_closed_loop import fitted_rows
    rows = len(fitted_rows(loop.train_gen.rows,
                           cell.config["workflow"]["selector"]))
    assert calls == [(rows, (), {})]
    assert loop.reference_fit[1].shape[0] == rows
    assert loop.reference_cv is None


def test_the_fitted_rows_are_the_stock_splitters(monkeypatch):
    """``fitted_rows`` states the rule of the program's own splitter: the
    split, then the balancer's cap on the training sample."""
    import numpy as np
    from benchmark.kinds.train_mesh_closed_loop import fitted_rows
    from transmogrifai_tpu.impl.tuning.splitters import DataBalancer
    y = (np.random.RandomState(1).rand(5000) < 0.2).astype(np.float32)
    for cap in (700, 10 ** 6):
        bal = DataBalancer(max_training_sample=cap)
        train_idx, _ = bal.split(len(y))
        want = train_idx[bal.pre_validation_prepare(y[train_idx]).indices]
        got = fitted_rows(len(y), {"seed": bal.seed,
                                   "reserve_test_fraction": 0.1,
                                   "max_training_sample": cap})
        np.testing.assert_array_equal(got, want)
        assert len(got) == min(cap, 4500)
    real = harness.load_cell(ROOT, harness.load_manifest(ROOT), REAL)
    stated = real.config["workflow"]["selector"]["max_training_sample"]
    assert stated == DataBalancer().max_training_sample == 1_000_000
