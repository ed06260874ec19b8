"""The per-layer metrics PR 36 adds: data files and manifest entries only.
Each file agrees with its entry, uses a reader kind that was there, and
reads the expected number from a small recorded set of spans: two trains,
the second with a stall in the one-hot fit and in the wait for the sweep.
And a traced train at a tiny size on the CPU, through the harness, reports
every one of them from the program's own spans."""
import json
import os
import shutil
import time
from types import SimpleNamespace

import pytest

from benchmark import harness, readers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
TRAIN = ["train-airline", "train-kddcup99", "train-nyctaxi",
         "train-airline-10m-mesh"]

#: metric -> (layer, unit, reader kind, the number the recording reads)
EXPECTED = {
    "prepare_labels_s": ("model selector sweep", "s", "span_sum", 400e-9),
    "prepare_split_s": ("model selector sweep", "s", "span_sum", 1600e-9),
    "prepare_balance_s": ("model selector sweep", "s", "span_sum", 1700e-9),
    "prepare_gather_s": ("model selector sweep", "s", "span_sum", 900e-9),
    "eval_rows_s": ("workflow", "s", "span_sum", 1100e-9),
    "eval_predict_s": ("workflow", "s", "span_sum", 600e-9),
    "eval_metrics_s": ("workflow", "s", "span_sum", 5000e-9),
    "sanity_sample_s": ("sanity checker", "s", "span_sum", 1000e-9),
    "sanity_stats_s": ("sanity checker", "s", "span_sum", 1500e-9),
    "sanity_collect_wait_s": ("sanity checker", "s", "span_sum", 2500e-9),
    "sanity_decide_s": ("sanity checker", "s", "span_sum", 500e-9),
    "fe_real_fit_s": ("feature engineering", "s", "span_sum", 1000e-9),
    "fe_real_stack_s": ("feature engineering", "s", "span_sum", 800e-9),
    "fe_real_stats_s": ("feature engineering", "s", "span_sum", 800e-9),
    "fe_real_fill_s": ("feature engineering", "s", "span_sum", 2100e-9),
    "closing_transform_s": ("workflow", "s", "span_sum", 3000e-9),
    "plan_probe_s": ("scoring plan", "s", "span_sum", 500e-9),
    "train_hbm_live_start_gb": ("memory", "GB", "span_attr_sum", 1.5),
    "train_hbm_live_end_gb": ("memory", "GB", "span_attr_sum",
                              3.0 + 25000e-9),
    "sweep_hbm_live_gb": ("memory", "GB", "span_attr_sum", 2.4),
    # the stalled train: 20 + 50 us waiting, 4 + 50 us in the one-hot fit
    "sweep_collect_wait_max_s": ("model selector sweep", "s", "span_sum_max",
                                 70000e-9),
    "fe_onehot_fit_max_s": ("feature engineering", "s", "span_sum_max",
                            54000e-9),
}
#: train-nyctaxi's Real and Integral fills run inside a planned device
#: segment (two device-capable stages in one layer): no host fill there
NO_NYCTAXI = [c for c in TRAIN if c != "train-nyctaxi"]
WORKLOADS = {"plan_probe_s": ["train-nyctaxi"],
             "fe_real_fill_s": NO_NYCTAXI, "fe_real_stack_s": NO_NYCTAXI}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(ROOT)


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _readings():
    with open(os.path.join(HERE, "data",
                           "recorded_spans_children.json")) as f:
        raw = json.load(f)
    return readers.Readings(
        ops=[tuple(o) for o in raw["ops"]],
        spans=[SimpleNamespace(**s) for s in raw["spans"]],
        epoch_ns=raw["epoch_ns"])


def test_the_entries_are_there_once_each(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    assert all(names.count(name) == 1 for name in EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_file_agrees_with_its_entry_and_uses_a_kind_that_was_there(
        manifest, name):
    layer, unit, kind, _ = EXPECTED[name]
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert dict(entry, workloads=None) == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_span", "layer": layer, "moves": "train_s",
        "workloads": None}
    # the cells PR 36 read it in, and whichever were listed since
    assert set(entry["workloads"]) >= set(WORKLOADS.get(name, TRAIN))
    spec = _spec(name)
    assert {k: spec[k] for k in entry} == entry
    assert set(spec) == set(entry) | {"what", "read"}
    assert spec["read"]["kind"] == kind
    # a kind of the readers' own or one of PR 24's three files: none added
    assert kind in readers.KINDS or os.path.exists(
        os.path.join(BENCH, "reader_kinds", kind + ".py"))
    # every cell it lists reports the end-to-end metric it moves
    (moved,) = [m for m in manifest["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_metric_reads_its_number_from_the_recorded_spans(name):
    spec = dict(_spec(name), bench_dir=BENCH)
    assert readers.read_metric(spec, _readings()) == pytest.approx(
        EXPECTED[name][3], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_spans_leaves_the_metric_out(name):
    """The parent of this PR has neither the child spans nor the
    attributes: the reader returns None and the line leaves the metric
    out. The two readings of spans that were there before read on."""
    r = _readings()
    old = {"workflow.train", "stage.fit", "stage.transform",
           "selector.prepare", "sweep.family", "sweep.collect",
           "selector.refit", "selector.evaluate", "plan.compile"}
    r.spans = [SimpleNamespace(**dict(vars(s), attrs={
        k: v for k, v in s.attrs.items() if not k.startswith("hbmLive")}))
        for s in r.spans if s.name in old]
    got = readers.read_metric(dict(_spec(name), bench_dir=BENCH), r)
    if name in ("fe_real_fit_s", "closing_transform_s",
                "sweep_collect_wait_max_s", "fe_onehot_fit_max_s"):
        assert got == pytest.approx(EXPECTED[name][3], rel=1e-9)
    else:
        assert got is None


def test_the_steps_add_up_to_their_regions_in_the_recording():
    """What the acceptance lines ask of a chip run, on the recording: the
    four prepare steps within 10 % of ``selector_prepare_s``, the three
    evaluation steps within 10 % of ``refit_eval_s``, the checker's four
    no more than ``sanity_s``."""
    r = _readings()

    def read(name):
        return readers.read_metric(dict(_spec(name), bench_dir=BENCH), r)
    prepare = sum(read(f"prepare_{k}_s")
                  for k in ("labels", "split", "balance", "gather"))
    assert prepare == pytest.approx(read("selector_prepare_s"), rel=0.1)
    evaluate = sum(read(f"eval_{k}_s") for k in ("rows", "predict",
                                                 "metrics"))
    assert evaluate == pytest.approx(read("refit_eval_s"), rel=0.1)
    checker = (read("sanity_sample_s") + read("sanity_stats_s")
               + read("sanity_collect_wait_s") + read("sanity_decide_s"))
    assert 0.9 * read("sanity_s") <= checker <= read("sanity_s")


#: the tests' tiny cells, one a train kind: (extension directory, config,
#: traffic, chips, the real cell it stands for)
TINY = {
    "binary": ("extension", "tiny-mixed", "train_tiny_closed_loop", 1,
               "train-airline"),
    "multiclass": ("extension_multiclass", "tiny-multiclass",
                   "train_multiclass_tiny_closed_loop", 1, "train-kddcup99"),
    "regression": ("extension_regression", "tiny-regression",
                   "train_regression_tiny_closed_loop", 1, "train-nyctaxi"),
    "mesh": ("extension_mesh", "tiny-mesh", "train_mesh_tiny_closed_loop", 2,
             "train-airline-10m-mesh"),
}


@pytest.fixture(params=sorted(TINY))
def tiny(request, tmp_path, manifest):
    """A copy of BENCHMARK.json and benchmark/ with one of the tests' tiny
    cells added, reading every metric that its real cell reads."""
    ext, config, traffic, chips, real = TINY[request.param]
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(HERE, "data", ext, sub)):
            shutil.copy(os.path.join(HERE, "data", ext, sub, f),
                        os.path.join(root, "benchmark", sub, f))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({
        "name": config, "source": "tests", "reduced": [],
        "file": f"benchmark/configs/{config}.json", "why": "tests"})
    m["workloads"].append({"name": "tiny", "config": config,
                           "traffic": traffic, "chips": chips,
                           "why": "tests"})
    for e in m["end_to_end"] + m["per_layer"]:
        if real in e.get("workloads", []):
            e["workloads"] = e["workloads"] + ["tiny"]
    return root, m, real


def test_a_traced_tiny_train_reports_every_metric_its_cell_lists(tiny):
    """Each train kind at a tiny size on the CPU, traced, through the
    harness: every metric of this PR that lists the kind's real cell reads
    a number from the program's own spans. `plan_probe_s` lists
    `train-nyctaxi` alone: there a layer holds two device-capable stages
    (the Real and the Integral fills), is planned at every train and
    probed, and its fills run inside the segment, so the host's
    `realvec.fill` / `realvec.stack` are not listed there."""
    root, m, real = tiny
    lines = []
    cell = harness.load_cell(root, m, "tiny")
    res = harness.run_cell(cell, 2 ** 31 + 36, 0.5, True,
                           time.perf_counter(), log=lines.append)
    assert res["correct"], lines
    got = {k: v["value"] for k, v in res["metrics"].items()}
    listed = {name for name in EXPECTED
              if real in WORKLOADS.get(name, TRAIN)}
    assert listed - set(got) == set()
    assert ("plan_probe_s" in listed) == (real == "train-nyctaxi")
    assert all(got[k] >= 0 for k in listed)
    assert 0 < got["train_hbm_live_start_gb"] < got["train_hbm_live_end_gb"]
    assert got["sweep_hbm_live_gb"] > got["train_hbm_live_start_gb"]
    assert got["sweep_collect_wait_max_s"] > 0
