"""The reader kinds and per-layer metrics that PR 24 adds, on a second small
recording: four family programs under one module name, a ``while`` with the
fusions of its body nested in it on the ops line, and two operations of which
the second stalls."""
import glob
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, readers, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
#: every kind that is a file beside the metrics: PR 24's three, and whatever
#: a later PR adds there (``readers.py`` says the directory is for that)
NEW_KINDS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(BENCH, "reader_kinds", "*.py")))
PR24_KINDS = ["device_op_by_span_order", "span_attr_sum", "span_sum_max"]


def _readings(raw):
    trace = tracered.Trace([tracered.Event(*e) for e in raw["events"]],
                           tuple(raw["anchor"]))
    return readers.Readings(
        ops=[tuple(o) for o in raw["ops"]],
        traced=[tuple(o) for o in raw["traced"]],
        spans=[SimpleNamespace(**s) for s in raw["spans"]],
        epoch_ns=raw["epoch_ns"], trace=trace)


@pytest.fixture()
def raw():
    with open(os.path.join(HERE, "data", "recorded_trace_nested.json")) as f:
        return json.load(f)


def _read(kind, spec, r):
    return readers.reader_for(kind, BENCH)(spec, r)


def test_the_kinds_this_pr_adds_are_files_beside_the_metrics():
    assert set(PR24_KINDS) <= set(NEW_KINDS)
    # a file under a name the readers' own table has would never be loaded
    assert not set(NEW_KINDS) & set(readers.KINDS)


@pytest.mark.parametrize("family,seconds", [
    ("OpRandomForest(Classifier|Regressor)", 4000e-9),
    ("OpGBT(Classifier|Regressor)", 2000e-9),
    ("OpLogisticRegression|OpLinearSVC|OpLinearRegression", 1500e-9),
    (".*", 7500e-9)])
def test_family_programs_are_dealt_to_spans_by_order(raw, family, seconds):
    spec = {"pattern": r"^jit_prog\(", "line": "XLA Modules",
            "span": r"sweep\.family", "attrs": {"family": family}}
    assert _read("device_op_by_span_order", spec,
                 _readings(raw)) == pytest.approx(seconds)


def test_a_split_family_takes_as_many_programs_as_it_launched(raw):
    """The exhaustion ladder split the forest's grid in two: its span says
    programs=2, so the second and third programs are the forest's."""
    spans = [s for s in raw["spans"] if s["name"] == "sweep.family"
             and s["ts_ns"] < 14000]
    by_family = {s["attrs"]["family"]: s for s in spans}
    by_family["OpRandomForestClassifier"]["attrs"]["programs"] = 2
    raw["spans"].remove(by_family["OpGBTClassifier"])
    by_family["OpLinearSVC"]["attrs"]["order"] = 2
    spec = {"pattern": r"^jit_prog\(", "line": "XLA Modules",
            "span": r"sweep\.family",
            "attrs": {"family": "OpRandomForestClassifier"}}
    assert _read("device_op_by_span_order", spec,
                 _readings(raw)) == pytest.approx(6000e-9)


def test_a_count_that_disagrees_pairs_nothing(raw):
    spec = {"pattern": r"^jit_prog\(", "line": "XLA Modules",
            "span": r"sweep\.family", "attrs": {"family": ".*"}}
    raw["events"].append(["/device:TPU:0", "XLA Modules", "jit_prog(55)",
                          9000, 100])
    assert _read("device_op_by_span_order", spec, _readings(raw)) is None


def test_spans_without_order_pair_nothing(raw):
    """The parent of PR 24: sweep.family spans carry no order."""
    for s in raw["spans"]:
        s["attrs"].pop("order", None)
    spec = {"pattern": r"^jit_prog\(", "line": "XLA Modules",
            "span": r"sweep\.family", "attrs": {"family": ".*"}}
    assert _read("device_op_by_span_order", spec, _readings(raw)) is None


def test_the_largest_operation_shows_the_stall(raw):
    r = _readings(raw)
    collect = {"name": r"plan\.collect"}
    assert _read("span_sum_max", collect, r) == pytest.approx(9000e-9)
    assert readers.span_sum(collect, r) == pytest.approx((300 + 9000) / 2
                                                         * 1e-9)
    assert _read("span_sum_max", {"name": r"plan\.stage_inputs"},
                 r) == pytest.approx(250e-9)


def test_an_attribute_is_summed_per_operation_and_scaled(raw):
    r = _readings(raw)
    spec = {"name": r"workflow\.train", "attr": "h2dBytes", "scale": 1e-9}
    assert _read("span_attr_sum", spec, r) == pytest.approx(0.49)
    for s in raw["spans"]:
        s["attrs"].pop("h2dBytes", None)
    assert _read("span_attr_sum", spec, _readings(raw)) is None


@pytest.mark.parametrize("kind", PR24_KINDS)
def test_a_new_kind_with_nothing_to_read_returns_nothing(kind):
    spec = {"kind": kind, "name": "x", "pattern": "x", "line": "x",
            "span": "x", "attr": "x"}
    assert _read(kind, spec, readers.Readings()) is None


# -- the metrics ------------------------------------------------------------

@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(ROOT)


PR24 = ["selector_prepare_s", "sweep_collect_wait_s", "sweep_forest_s",
        "sweep_gbt_s", "sweep_linear_s", "refit_fit_s", "refit_eval_s",
        "fe_onehot_fit_s", "fe_onehot_transform_s", "fe_combine_s",
        "fe_onehot_encode_s", "fe_onehot_expand_s", "train_h2d_gb",
        "score_stage_inputs_s", "score_collect_wait_s", "score_h2d_gb",
        "score_collect_wait_max_s", "score_stage_inputs_max_s"]


@pytest.mark.parametrize("name", PR24)
def test_each_new_metric_loads_with_its_cell_and_agrees_with_the_manifest(
        manifest, name):
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"]
    for cell_name in entry["workloads"]:
        cell = harness.load_cell(ROOT, manifest, cell_name)
        (spec,) = [s for s in cell.per_layer if s["name"] == name]
        assert spec["workloads"] == entry["workloads"]
        assert spec["better"] == "lower" and len(spec["what"]) > 20
        # its reader is there, and finds nothing in an empty run
        assert readers.read_metric(spec, readers.Readings()) is None


def test_the_new_metrics_read_the_recording(raw, manifest):
    r = _readings(raw)
    got = {}
    for cell_name in ("train-airline", "score-higgs"):
        for spec in harness.load_cell(ROOT, manifest, cell_name).per_layer:
            if spec["name"] in PR24:
                got[spec["name"]] = readers.read_metric(spec, r)
    ns = 1e-9
    assert got["selector_prepare_s"] == pytest.approx(400 * ns)
    assert got["sweep_collect_wait_s"] == pytest.approx(6300 * ns)
    assert (got["sweep_forest_s"], got["sweep_gbt_s"],
            got["sweep_linear_s"]) == pytest.approx(
                (4000 * ns, 2000 * ns, 1500 * ns))
    sweep_s = readers.device_op_sum({"pattern": r"^jit_prog\(",
                                     "line": "XLA Modules"}, r)
    assert got["sweep_forest_s"] + got["sweep_gbt_s"] \
        + got["sweep_linear_s"] == pytest.approx(sweep_s)
    assert got["refit_fit_s"] == pytest.approx(1050 * ns)
    assert got["refit_eval_s"] == pytest.approx(1250 * ns)
    assert got["train_h2d_gb"] == pytest.approx(0.49)
    assert got["score_collect_wait_max_s"] == pytest.approx(9000 * ns)
    assert got["score_stage_inputs_max_s"] == pytest.approx(250 * ns)
    # the recording has no one-hot stage and no workflow.score
    assert got["fe_onehot_encode_s"] is None and got["score_h2d_gb"] is None
