"""The kind ``train_forest_closed_loop`` at test size on the CPU, from files
added to a temporary copy of the benchmark: a forest wins, a sound run is
``correct`` and prints every number beside its limit; each control, and a
refit whose exact leaf pass was skipped or ran on the split-search sample,
comes out not correct on the number meant for it; the traced run reads the
metrics that spans give; the two reader kinds on a made-up trace;
and what PR 39 adds to the manifest."""
import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

from benchmark import forest_readers, harness, readers, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXT = os.path.join(HERE, "data", "extension_forest")
CELL = "train-tiny-forest"
BOOSTED = "train-tiny-boosted"
REAL = "train-higgs"
#: the cell's own metrics (a tree winner's programs), the readers PR 39 first
#: read here, and PR 36's host and live-memory readers, which list the cell
MINE = ("hg_refit_grow_s", "hg_eval_descent_s", "hg_closing_descent_s",
        "hg_descent_roofline")
NEW = MINE + ("sweep_forest_s", "sweep_gbt_s", "sweep_linear_s",
              "refit_fit_s", "refit_eval_s", "selector_prepare_s")
HOST = ("fe_real_fit_s", "fe_real_stack_s", "fe_real_stats_s",
          "fe_real_fill_s", "sanity_sample_s", "sanity_stats_s",
          "sanity_collect_wait_s", "sanity_decide_s", "prepare_labels_s",
          "prepare_split_s", "prepare_balance_s", "prepare_gather_s",
          "eval_rows_s", "eval_predict_s", "eval_metrics_s",
          "closing_transform_s", "train_hbm_live_start_gb",
          "train_hbm_live_end_gb", "sweep_hbm_live_gb")
SHARED = ("refit_s", "fe_s", "sanity_s", "sweep_s", "train_device_busy_s",
          "train_device_idle_pct", "train_hbm_peak_gb")
FOREST = "OpRandomForestClassifier"


@pytest.fixture(scope="module", autouse=True)
def small_split_sample():
    """The growers' split-search sample cut to the tiny configuration's
    ``split_search_sample``, so that a refit's trees are grown on fewer rows
    than its leaves are summed over, as at the cell's size (65 536 of a
    million). Every train of this module traces with it."""
    from transmogrifai_tpu.models import trees
    cfg = json.load(open(os.path.join(EXT, "configs", "tiny-forest.json")))
    kept, trees._HIST_SAMPLE = (
        trees._HIST_SAMPLE,
        int(cfg["workflow"]["selector"]["split_search_sample"]))
    yield
    trees._HIST_SAMPLE = kept


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("forest_bench"))
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
        "name": "tiny-forest", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-forest.json", "why": "tests"})
    m["configs"].append({
        "name": "tiny-boosted", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-boosted.json", "why": "tests"})
    for cell, config in ((CELL, "tiny-forest"), (BOOSTED, "tiny-boosted")):
        m["workloads"].append(
            {"name": cell, "config": config,
             "traffic": "train_forest_tiny_closed_loop", "chips": 1,
             "why": "tests"})
        for e in m["end_to_end"] + m["per_layer"]:
            if REAL in e.get("workloads", []):
                e["workloads"].append(cell)
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
                 "quarantined_fits", "winner_is_stated_family",
                 "winner_max_depth_off", "winner_depth_margin",
                 "refit_edges_max_rel_diff", "feature_vector_max_abs_diff",
                 "score_max_abs_diff", "auroc", "linear_auroc_gap",
                 "refit_leaf_max_abs_diff", "refit_leaves_compared",
                 "refit_split_gain_rel_diff", "refit_root_splits_compared",
                 "cv_metric_abs_diff", "planned_vs_eager_max_abs_diff",
                 "fault_kinds_counted"):
        assert any(ln.startswith(f"check {name}:") for ln in lines), name
    assert any(ln.startswith(f"winner {FOREST}") and '"maxDepth": 12' in ln
               for ln in lines)


@pytest.fixture(scope="module")
def checked(extended):
    """One loop after its checks, with every control's numbers."""
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    ctx = harness.Context(cell, 2 ** 31 + 7, 0.0, False,
                          harness.Monitor().install(), lambda s: None)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    loop.setup()
    loop.prepare_op()
    loop.op()
    sound = {c.name: c for c in loop.check()}
    controls = {name: {c.name: c for c in checks}
                for name, checks in loop.controls().items()}
    return sound, controls


@pytest.mark.parametrize("control, fails, factor", [
    ("bf16_descent", ("score_max_abs_diff",), 1e3),
    ("sample_leaves", ("refit_leaf_max_abs_diff",), 1e3),
    ("roots_moved", ("refit_split_gain_rel_diff",), 2),
    ("shuffled_label", ("auroc", "linear_auroc_gap"), None)])
def test_a_control_fails_the_number_meant_for_it(checked, control, fails,
                                                 factor):
    sound, controls = checked
    assert all(c.ok for c in sound.values()), [
        c.line() for c in sound.values() if not c.ok]
    for name in fails:
        got = controls[control][name]
        assert not got.ok, got.line()
        if factor:
            assert got.value > factor * sound[name].value
    # and leaves the numbers meant for the other controls alone
    others = {"bf16_descent": ("refit_leaf_max_abs_diff", "auroc"),
              "sample_leaves": ("score_max_abs_diff", "auroc"),
              "roots_moved": ("score_max_abs_diff", "auroc"),
              "shuffled_label": ("score_max_abs_diff",
                                 "refit_leaf_max_abs_diff")}[control]
    for name in others:      # (a shuffled label may hand the win to a line)
        if name in controls[control]:
            assert controls[control][name].ok, controls[control][name].line()


def test_a_boosted_winner_is_held_round_by_round(extended):
    """The same kind with a boosted winner: its refit has no leaf pass, so
    its rounds are retrained on the rows they were grown on; a reference
    whose running score never moves is told apart."""
    root, m = extended
    cell = harness.load_cell(root, m, BOOSTED)
    lines = []
    ctx = harness.Context(cell, 7, 0.0, False, harness.Monitor().install(),
                          lines.append)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    loop.setup()
    loop.prepare_op()
    loop.op()
    sound = {c.name: c for c in loop.check()}
    assert all(c.ok for c in sound.values()), [
        c.line() for c in sound.values() if not c.ok]
    assert any("rounds retrained" in ln for ln in lines)
    controls = loop.controls()
    assert set(controls) == {"bf16_descent", "frozen_score", "roots_moved",
                             "splits_moved", "edges_without_pad_rows",
                             "fold_reversed", "shuffled_label"}
    moved = {c.name: c for c in controls["roots_moved"]}
    assert not moved["refit_split_gain_rel_diff"].ok
    # every node that splits is held, not the roots alone: a sound grower's
    # largest shortfall is its histograms' rounding in a small node
    assert sound["refit_splits_compared"].value \
        > 3 * sound["refit_root_splits_compared"].value
    assert sound["refit_split_gain_rel_diff"].value < 0.05
    assert sound["refit_split_gain_mean_rel_diff"].value < 1e-3
    deep = {c.name: c for c in controls["splits_moved"]}
    assert not deep["refit_split_gain_rel_diff"].ok
    assert not deep["refit_split_gain_mean_rel_diff"].ok
    assert deep["score_max_abs_diff"].ok
    # the edge table is rebuilt from the rows by the stated rule
    assert sound["refit_edges_max_rel_diff"].ok
    (edges,) = controls["edges_without_pad_rows"]
    assert edges.name == "refit_edges_max_rel_diff" and not edges.ok
    assert edges.value > 100 * max(sound["refit_edges_max_rel_diff"].value,
                                   1e-7)
    # one fold of three scored the wrong way round moves the sweep's number
    (cv,) = controls["fold_reversed"]
    assert cv.name == "cv_metric_abs_diff"
    assert cv.value > 3 * sound["cv_metric_abs_diff"].value
    frozen = {c.name: c for c in controls["frozen_score"]}
    assert not frozen["refit_leaf_max_abs_diff"].ok
    assert frozen["refit_leaf_max_abs_diff"].value \
        > 1e3 * sound["refit_leaf_max_abs_diff"].value
    assert frozen["score_max_abs_diff"].ok and frozen["auroc"].ok
    low = {c.name: c for c in controls["bf16_descent"]}
    assert not low["score_max_abs_diff"].ok
    assert low["refit_leaf_max_abs_diff"].ok


def _no_leaf_pass(monkeypatch):
    """A refit that leaves the exact leaf pass out: no row reaches a leaf,
    so the grown forest goes out with the values of empty leaves."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees
    real = trees._exact_leaf_stats_chain

    def skipped(codes, feat_lv, bin_lv, base_lv, stats, w, n_bins):
        return real(codes, feat_lv, bin_lv, base_lv, stats,
                    jnp.zeros_like(w), n_bins)

    monkeypatch.setattr(trees, "_exact_leaf_stats_chain", skipped)


def _leaf_pass_on_the_sample(monkeypatch):
    """A refit whose leaf pass reads the split-search sample only: every
    other row's weight is zero."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees
    real = trees._exact_leaf_stats_chain

    def sampled(codes, feat_lv, bin_lv, base_lv, stats, w, n_bins):
        keep = jnp.zeros(codes.shape[0]).at[
            trees._sample_rows(codes.shape[0], trees._HIST_SAMPLE)].set(1.0)
        return real(codes, feat_lv, bin_lv, base_lv, stats, w * keep, n_bins)

    monkeypatch.setattr(trees, "_exact_leaf_stats_chain", sampled)


@pytest.mark.parametrize("plant, fails", [
    (_no_leaf_pass, ("refit_leaf_max_abs_diff", "auroc")),
    (_leaf_pass_on_the_sample, ("refit_leaf_max_abs_diff",)),
], ids=lambda v: getattr(v, "__name__", None))
def test_a_broken_refit_comes_out_not_correct(extended, monkeypatch, plant,
                                              fails):
    """The leaf pass is traced into the refit's one program: the plant goes
    in before that program is traced anew, and out with it afterwards."""
    from transmogrifai_tpu.models import trees
    root, m = extended
    plant(monkeypatch)
    trees._fit_rf_batch.clear_cache()
    try:
        _, res, lines = _run(root, m)
    finally:
        monkeypatch.undo()
        trees._fit_rf_batch.clear_cache()
    assert res["correct"] is False
    for name in fails:
        assert _failed(lines, name), (name, lines)
    assert not _failed(lines, "score_max_abs_diff")


def test_set_up_runs_the_traffics_warm_ops(extended, monkeypatch):
    """``warm_ops`` whole trains before the window opens, none of them
    reported; a traffic file without the key runs the one warm train."""
    root, m = extended
    cell = harness.load_cell(root, m, BOOSTED)
    assert "warm_ops" not in cell.traffic
    monkeypatch.setitem(cell.traffic, "warm_ops", 3)
    ctx = harness.Context(cell, 3, 0.0, False, harness.Monitor().install(),
                          lambda s: None)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    ran = []
    real = type(loop).op
    monkeypatch.setattr(type(loop), "op",
                        lambda self: (ran.append(1), real(self))[1])
    loop.setup()
    assert len(ran) == 3 and loop.reports == []
    assert loop.warm_report["fits"] > 0 and loop.model is not None


def test_another_winner_than_the_stated_one_is_not_correct(extended,
                                                           monkeypatch):
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    monkeypatch.setitem(cell.config["workflow"], "stated_winner",
                        {"family": "OpGBTClassifier", "maxDepth": 6})
    result = harness.run_cell(cell, 5, 0.5, False, time.perf_counter(),
                              log=(lines := []).append)
    assert result["correct"] is False
    assert _failed(lines, "winner_is_stated_family")
    assert _failed(lines, "winner_max_depth_off")


def test_a_traced_run_reads_the_span_metrics_this_pr_adds(extended):
    root, m = extended
    cell, res, lines = _run(root, m, trace=True)
    got = res["metrics"]
    for name in ("refit_fit_s", "refit_eval_s", "selector_prepare_s"):
        assert got[name]["value"] > 0, name
    assert got["refit_fit_s"]["value"] < got["refit_s"]["value"] \
        if "refit_s" in got else True
    # PR 36's host steps and live bytes read in this cell under their names
    for name in HOST:
        assert got[name]["value"] >= 0, name
    # no device plane on the CPU: the device metrics say nothing
    assert not (set(MINE) | {"sweep_forest_s"}) & set(got)
    assert {s["name"] for s in cell.per_layer} \
        >= set(NEW) | set(HOST) | set(SHARED)


# ---------------------------------------------------------------------------
# the reader kinds, on a made-up trace of one device plane
# ---------------------------------------------------------------------------

MS = 1e6
PREDICT = "jit__predict_rf_chain_batch"


def _one_train():
    """One traced train of 100 ms. The refit's program, then three
    predicts of one name: 8 and 2 ms launched by ``evaluate.predict``, 10 ms
    by ``predict.parts``. The first predict's program event starts 3 ms
    before its first operation (it waits for its rows)."""
    plane = "/device:TPU:0"
    events = [
        tracered.Event(plane, "XLA Modules", "jit__fit_rf_batch(7)",
                       10.0 * MS, 12.0 * MS),
        tracered.Event(plane, "XLA Ops", "%fusion.1 = f32[8] fusion(...)",
                       10.0 * MS, 12.0 * MS)]
    for i, (at, dur, wait) in enumerate(
            [(40.0, 8.0, 3.0), (55.0, 2.0, 0.0), (70.0, 10.0, 0.0)]):
        events.append(tracered.Event(plane, "XLA Modules",
                                     f"{PREDICT}({20 + i})",
                                     (at - wait) * MS, (dur + wait) * MS))
        events.append(tracered.Event(plane, "XLA Ops",
                                     "%custom-call.9 = descent(...)",
                                     at * MS, dur * MS))
    tree = {"trees": 50, "depth": 12, "features": 28, "treeChunks": 2}
    spans = [
        SimpleNamespace(name="refit.grow", ts_ns=int(9 * MS),
                        dur_ns=int(1 * MS),
                        attrs={"family": FOREST, "trees": 50, "depth": 12,
                               "slots": 256, "sampleRows": 65536}),
        SimpleNamespace(name="evaluate.predict", ts_ns=int(30 * MS),
                        dur_ns=int(1 * MS),
                        attrs=dict(tree, rows=3600000, split="train")),
        SimpleNamespace(name="evaluate.predict", ts_ns=int(50 * MS),
                        dur_ns=int(1 * MS),
                        attrs=dict(tree, rows=400000, split="holdout")),
        SimpleNamespace(name="predict.parts", ts_ns=int(65 * MS),
                        dur_ns=int(20 * MS),
                        attrs=dict(tree, rows=4000000, family=FOREST))]
    return readers.Readings(
        ops=[(0, int(100 * MS))], traced=[(0, int(100 * MS))], spans=spans,
        epoch_ns=0, trace=tracered.Trace(events, (0.0, 0.0)))


def _spec(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       name + ".json")))


def test_the_predicts_are_dealt_to_the_spans_that_launched_them():
    from benchmark.kinds import train_forest_closed_loop  # noqa: F401
    r = _one_train()
    # what ran inside the programs, not the first one's 3 ms of waiting
    assert readers.read_metric(_spec("hg_eval_descent_s"), r) \
        == pytest.approx(10e-3)
    assert readers.read_metric(_spec("hg_closing_descent_s"), r) \
        == pytest.approx(10e-3)
    assert readers.read_metric(_spec("hg_refit_grow_s"), r) \
        == pytest.approx(12e-3)
    # a predict more than the spans account for: the pairing would be a guess
    r.trace.events.append(tracered.Event(
        "/device:TPU:0", "XLA Modules", f"{PREDICT}(31)", 90.0 * MS, MS))
    assert readers.read_metric(_spec("hg_eval_descent_s"), r) is None
    # a boosted winner's predict goes by its own name
    gbt = _one_train()
    gbt.trace = tracered.Trace([
        e if PREDICT not in e.name else tracered.Event(
            e.plane, e.line, e.name.replace("_rf_", "_gbt_"), e.start_ns,
            e.dur_ns) for e in gbt.trace.events], (0.0, 0.0))
    assert readers.read_metric(_spec("hg_eval_descent_s"), gbt) \
        == pytest.approx(10e-3)


def test_the_roofline_is_the_bytes_the_predicts_must_move():
    from benchmark.kinds import train_forest_closed_loop  # noqa: F401
    r = _one_train()
    spec = _spec("hg_descent_roofline")
    assert spec["unit"] == "%" and "LOWER BOUND" in spec["what"]
    nbytes = (3600000 + 400000 + 4000000) * (28 + 1) * 4
    assert sum(forest_readers.descent_bytes(s.attrs)
               for s in r.spans[1:]) == nbytes
    assert forest_readers.span_forest_bytes_roofline(
        spec["read"], r, device_kind="TPU v5 lite") == pytest.approx(
            100 * nbytes / 20e-3 / 819e9)
    # the parent's spans lack the attribute: nothing to read
    for s in r.spans:
        s.attrs.pop("features", None)
    assert forest_readers.span_forest_bytes_roofline(
        spec["read"], r, device_kind="TPU v5 lite") is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_finds_nothing_in_an_empty_run(name):
    spec = _spec(name)
    assert (spec["workloads"] == [REAL] if name in MINE
            else REAL in spec["workloads"])
    assert spec["moves"] == "train_s"
    assert len(spec["what"]) > 20
    from benchmark.kinds import train_forest_closed_loop  # noqa: F401
    assert readers.read_metric(spec, readers.Readings()) is None


def test_the_cell_loads_with_its_files_and_its_metrics():
    m = harness.load_manifest(ROOT)
    cell = harness.load_cell(ROOT, m, REAL)
    assert cell.chips == 1 and cell.config["name"] == "higgs-train-8m"
    assert cell.config["rows"] == 8_000_000
    assert cell.config["source_rows"] == 11_000_000
    assert cell.config["holdout_rows"] == 500_000
    assert cell.config["reduced"] == ["rows", "label_rule"]
    assert cell.config["architecture"] is None
    assert cell.traffic["kind"] == "train_forest_closed_loop"
    assert {e["name"] for e in cell.end_to_end} == {"train_s", "setup_s"}
    names = {s["name"] for s in cell.per_layer}
    assert names >= set(NEW) | set(SHARED) | {"setup_compile_s"} | set(HOST)
    assert {n for n in names if n.startswith("hg_")} == set(MINE)
    (entry,) = [c for c in m["configs"]
                if c["name"] == cell.config["name"]]
    assert entry["reduced"] == cell.config["reduced"]
    # the traffic is train-airline's, letter for letter, ...
    airline = harness.load_cell(ROOT, m, "train-airline")
    for key in ("clients", "rows", "min_ops", "traced_ops", "reports",
                "collect_garbage_between_ops"):
        assert cell.traffic[key] == airline.traffic[key], key
    # ... but for the allocator's two thresholds, which are four times
    # train-airline's so that this cell's largest host array (the Real
    # block: rows x 56 float32 columns) stays under the mapping threshold as
    # train-airline's own arrays do under theirs, and for the trains that
    # set-up runs before the window opens
    mine, theirs = (t["process_env"]["set"]
                    for t in (cell.traffic, airline.traffic))
    assert set(mine) == set(theirs)
    assert all(int(mine[k]) == 4 * int(theirs[k]) for k in theirs)
    block = cell.config["rows"] * 2 * len(cell.config["columns"]) * 4
    assert int(theirs["MALLOC_MMAP_THRESHOLD_"]) < block \
        < int(mine["MALLOC_MMAP_THRESHOLD_"])
    assert cell.traffic["process_env"]["why"].startswith(
        airline.traffic["process_env"]["why"])
    assert cell.traffic["warm_ops"] == 3 and "warm_ops" not in airline.traffic
    # the stock selector as airline-1m states it, and the source's columns
    mine = cell.config["workflow"]
    theirs = airline.config["workflow"]
    assert mine["prepare"] == theirs["prepare"]
    assert mine["expected_fits"] == theirs["expected_fits"] == 135
    for key in ("validation", "folds", "models", "seed",
                "reserve_test_fraction"):
        assert mine["selector"][key] == theirs["selector"][key], key
    score = harness.load_cell(ROOT, m, "score-higgs")
    assert [(c["name"], c["type"]) for c in cell.config["columns"]] == [
        (c["name"], c["type"]) for c in score.config["columns"]]
    # every limit of the comparison is stated with its reason
    check = cell.config["check"]
    for key in ("score_max_abs_diff", "refit_leaf_max_abs_diff",
                "refit_split_gain_rel_diff", "cv_metric_abs_diff",
                "linear_auroc_gap_floor", "auroc_floor"):
        assert key in check and key.replace("_floor", "") in check[
            "reasons"], key
    assert set(mine["stated_winner"]) == {"family", "maxDepth"}


def test_the_stated_rows_are_the_programs(monkeypatch):
    """The configuration states, for the reference, the rows a refit pads
    its fit matrix to and the growers' split-search sample."""
    from transmogrifai_tpu.impl.tuning.splitters import DataBalancer
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.utils.padding import bucket_for
    monkeypatch.undo()
    cell = harness.load_cell(ROOT, harness.load_manifest(ROOT), REAL)
    sel = cell.config["workflow"]["selector"]
    assert sel["max_training_sample"] == DataBalancer().max_training_sample
    assert sel["refit_padded_rows"] == bucket_for(sel["max_training_sample"])
    tiny = json.load(open(os.path.join(EXT, "configs", "tiny-forest.json")))
    kept = tiny["rows"] - round(tiny["rows"] * 0.1)
    assert tiny["workflow"]["selector"]["refit_padded_rows"] \
        == bucket_for(kept)


def test_the_generator_draws_rows_only_from_the_seed():
    import numpy as np
    from benchmark import datagen_higgs
    cfg = json.load(open(os.path.join(EXT, "configs", "tiny-forest.json")))
    a = datagen_higgs.generate(cfg, 2 ** 31 + 5, 3000)
    b = datagen_higgs.generate(cfg, 2 ** 31 + 5, 3000)
    c = datagen_higgs.generate(cfg, 2 ** 31 + 6, 3000)
    assert all(np.array_equal(a.columns[k], b.columns[k]) for k in a.columns)
    assert np.array_equal(a.label, b.label)
    assert not np.array_equal(a.label, c.label)
    assert set(a.types.values()) == {"Real"} and a.true_prob is not None
    # a window at a column's own median reads no linear trend
    x = a.columns["m_bb"]
    w = datagen_higgs.window(x, cfg["label_rule"]["windows"]["m_bb"])
    assert 0.0 < w.min() and w.max() <= 1.0
    assert abs(np.corrcoef(np.log(x), w)[0, 1]) < 0.1


def test_a_cascade_alternates_with_every_cut():
    """Rows leave the cascade at the first cut they fail, with that step's
    logit; a row that passes every cut gets the last word."""
    import numpy as np
    from benchmark import datagen_higgs
    cols = {"a": np.array([1.0, 3.0, 1.0, 1.0], np.float32),
            "b": np.array([1.0, 1.0, 3.0, 1.0], np.float32),
            "t": np.array([1.0, 1.0, 1.0, 0.0], np.float32)}
    rule = {"kind": "mass_windows", "intercept": 0.0, "terms": [],
            "windows": {"a": {"center": 1.0, "width": 0.2},
                        "b": {"center": 1.0, "width": 0.2}},
            "cascade": {"passed_logit": 5.0, "steps": [
                {"windows": ["a"], "exit": "out", "logit": -2.0},
                {"windows": ["b"], "exit": "out", "logit": 2.0},
                {"indicator": {"column": "t", "above": 0.5},
                 "exit": "out", "logit": -4.0}]}}
    np.testing.assert_array_equal(datagen_higgs.logit(rule, cols),
                                  [5.0, -2.0, 2.0, -4.0])
