"""The kind ``train_text_closed_loop`` at test size on the CPU, from files
added to a temporary copy of the benchmark: the cell ``train-tweets`` loads
with its files; a whole tiny run is ``correct`` and prints every number
beside its limit; each control comes out not correct on the number meant for
it; a program without the ``text.hash`` span is refused at once; the traced
run reads the metrics that spans give; the generator gives the same
rows for the same seed and the stated share of rows that are not ASCII; and
``reference_text.py`` imports nothing of the program."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import datagen_text, harness, manifest, reference_text

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
EXT = os.path.join(HERE, "data", "extension_text")
CELL = "train-tiny-tweets"
REAL = "train-tweets"
TEXT = ("tx_fe_text_fit_s", "tx_fe_text_hash_s", "tx_fe_text_concat_s",
        "tx_fe_text_py_rows", "tx_fe_text_s")
# the two device readers PR 41 first read here, which list the cell among
# others, and the host's readers that PR 47 listed it in
COPIES = ("sweep_gbt_s", "refit_roofline")
SHARED = ("refit_s", "fe_s", "sanity_s", "sweep_s", "train_device_busy_s",
          "train_device_idle_pct", "train_hbm_peak_gb")
HOST = ("fe_onehot_fit_s", "fe_combine_s", "sanity_sample_s",
        "sanity_stats_s", "sanity_collect_wait_s", "sanity_decide_s",
        "selector_prepare_s", "refit_fit_s", "refit_eval_s",
        "closing_transform_s", "train_hbm_live_start_gb",
        "train_hbm_live_end_gb", "sweep_hbm_live_gb")
DEVICE = set(COPIES) | {"sweep_linear_s", "sweep_forest_s"}


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("text_bench"))
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
        "name": "tiny-tweets", "source": "tests", "reduced": [],
        "file": "benchmark/configs/tiny-tweets.json", "why": "tests"})
    m["workloads"].append(
        {"name": CELL, "config": "tiny-tweets",
         "traffic": "train_text_tiny_closed_loop", "chips": 1,
         "why": "tests"})
    for e in m["end_to_end"] + m["per_layer"]:
        if REAL in e.get("workloads", []):
            e["workloads"].append(CELL)
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root, m


def _run(root, manifest_, trace=False, seed=2 ** 31 + 11):
    lines = []
    cell = harness.load_cell(root, manifest_, CELL)
    result = harness.run_cell(cell, seed, 0.5, trace, time.perf_counter(),
                              log=lines.append)
    return cell, result, lines


# ---------------------------------------------------------------------------
# the manifest: the cell loads with its files
# ---------------------------------------------------------------------------

def test_the_cell_loads_with_its_files():
    m = manifest.load_manifest(ROOT)
    w, config, traffic, bench_dir = manifest.cell_files(ROOT, m, REAL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "sentiment140-512k", "train_text_closed_loop", 1)
    assert os.path.isfile(config) and os.path.isfile(traffic)
    cell = harness.load_cell(ROOT, m, REAL)
    assert harness.loop_for(cell.traffic["kind"]).__module__.endswith(
        "train_text_closed_loop")
    assert [e["name"] for e in cell.end_to_end] == ["train_s", "setup_s"]
    assert {s["name"] for s in cell.per_layer} >= set(
        TEXT + COPIES + SHARED + HOST) | DEVICE
    cfg = cell.config
    assert cfg["architecture"] is None and cfg["problem"] == "binary"
    assert [(c["name"], c["type"]) for c in cfg["columns"]] == [
        ("date", "DateTime"), ("flag", "PickList"), ("user", "ID"),
        ("text", "Text")]
    entry = next(c for c in m["configs"] if c["name"] == w["config"])
    assert entry["reduced"] == cfg["reduced"] == ["rows", "label_rule"]
    assert set(cfg["assumed"]) >= {"rows", "holdout_rows", "label"}
    # the forest cell's allocator thresholds, letter for letter
    forest = manifest.load_json(os.path.join(
        bench_dir, "traffic", "train_forest_closed_loop.json"))
    assert cell.traffic["process_env"]["set"] == forest["process_env"]["set"]
    assert (cell.traffic["min_ops"], cell.traffic["warm_ops"]) == (6, 3)


@pytest.mark.parametrize("name", TEXT + COPIES)
def test_a_metric_of_the_cell_is_a_data_file_over_a_reader_the_benchmark_has(
        name):
    from benchmark import readers
    from benchmark.kinds import train_text_closed_loop  # noqa: F401
    m = manifest.load_manifest(ROOT)
    entry = next(e for e in m["per_layer"] if e["name"] == name)
    assert (entry["workloads"] == [REAL] if name in TEXT
            else REAL in entry["workloads"])
    assert entry["moves"] == "train_s"
    spec = manifest.load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                           name + ".json"))
    assert callable(readers.reader_for(spec["read"]["kind"]))
    # a reader that finds nothing to read says nothing and does not raise
    assert readers.read_metric(spec, readers.Readings()) is None


def test_the_reference_imports_nothing_of_the_program():
    src = open(reference_text.__file__).read()
    assert "transmogrifai" not in src.replace("TransmogrifAI", "")
    assert "import jax" not in src


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_config():
    return json.load(open(os.path.join(EXT, "configs", "tiny-tweets.json")))


def test_the_same_seed_gives_the_same_rows(tiny_config):
    a = datagen_text.generate(tiny_config, 2 ** 31 + 5, 4000)
    b = datagen_text.generate(tiny_config, 2 ** 31 + 5, 4000)
    c = datagen_text.generate(tiny_config, 2 ** 31 + 6, 4000)
    for name in a.columns:
        assert np.array_equal(a.columns[name], b.columns[name]), name
    assert np.array_equal(a.label, b.label)
    assert not np.array_equal(a.columns["text"], c.columns["text"])
    assert a.types == {"date": "DateTime", "flag": "PickList", "user": "ID",
                       "text": "Text"}


def test_the_rows_are_what_the_file_states():
    config = manifest.load_json(manifest.cell_files(
        ROOT, manifest.load_manifest(ROOT), REAL)[1])
    col = next(c for c in config["columns"] if c["name"] == "text")
    g = datagen_text.generate(config, 7, 20000)
    docs = g.columns["text"]
    assert max(map(len, docs)) <= col["max_chars"]
    assert all(d for d in docs)
    share = np.mean([not d.isascii() for d in docs])
    assert abs(share - col["non_ascii_share"]) < 0.004
    words = np.array([len(reference_text.tokenize(d)) for d in docs])
    assert words.min() >= 1 and words.max() <= col["words"]["max"]
    assert 10.0 < words.mean() < 14.0
    assert any(d != d.lower() for d in docs)            # capitals
    assert any(", " in d for d in docs) and any("... " in d for d in docs)
    assert set(g.columns["flag"]) == {"NO_QUERY"}
    # one object a level, the commonest handle first
    users, counts = np.unique(g.columns["user"], return_counts=True)
    assert users[np.argmax(counts)] == "user_1"
    assert 0.45 < g.label.mean() < 0.55
    # a label that follows the rule: the rule's own score ranks it
    from benchmark import reference
    assert reference.auroc(g.true_prob, g.label) > 0.7


# ---------------------------------------------------------------------------
# whole runs at test size
# ---------------------------------------------------------------------------

def test_a_sound_run_is_correct_and_prints_every_number(extended):
    root, m = extended
    _, res, lines = _run(root, m)
    assert res["correct"], lines
    assert set(res["metrics"]) == {"train_s", "setup_s"}
    for name in ("compiles_in_window", "fits", "fits_finite",
                 "quarantined_fits", "text_path_native", "text_rows_native",
                 "text_rows_native_off", "feature_vector_max_abs_diff",
                 "train_vector_max_abs_diff", "score_max_abs_diff", "auroc",
                 "refit_coef_max_abs_diff", "refit_score_max_abs_diff",
                 "cv_metric_abs_diff", "planned_vs_eager_max_abs_diff",
                 "fault_kinds_counted"):
        assert any(ln.startswith(f"check {name}:") for ln in lines), name
    # 512 bins + null, 20 levels + OTHER + null, 1 + OTHER + null, 8
    assert any(ln.startswith("reference_text: 546 derived columns")
               for ln in lines)
    assert any(ln.startswith("text_path native") for ln in lines)


@pytest.fixture(scope="module")
def checked(extended):
    """One loop after its checks, with every control's numbers."""
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    lines = []
    ctx = harness.Context(cell, 2 ** 31 + 7, 0.0, False,
                          harness.Monitor().install(), lines.append)
    loop = harness.loop_for(cell.traffic["kind"])(ctx)
    loop.setup()
    loop.prepare_op()
    loop.op()
    sound = {c.name: c for c in loop.check()}
    controls = {name: {c.name: c for c in checks}
                for name, checks in loop.controls().items()}
    # the held-out score runs with spans on (and with them the registry):
    # a module's fixture ends before conftest's guard looks
    from transmogrifai_tpu import observability
    observability.reset()
    return sound, controls, lines


@pytest.mark.parametrize("control, fails, holds, factor", [
    ("bf16", ("score_max_abs_diff", "refit_coef_max_abs_diff",
              "refit_score_max_abs_diff"), ("cv_metric_abs_diff", "auroc"),
     20),
    ("other_modulus", ("feature_vector_max_abs_diff",), (), None),
    ("no_lower_case", ("feature_vector_max_abs_diff",), (), None),
    ("binary_counts", ("feature_vector_max_abs_diff",), (), None),
    ("fold_reversed", ("cv_metric_abs_diff",), (), None),
    ("sweep_path_refit", ("refit_coef_max_abs_diff",
                          "refit_score_max_abs_diff"), (), 20)])
def test_a_control_fails_the_number_meant_for_it(checked, control, fails,
                                                 holds, factor):
    sound, controls, _ = checked
    assert all(c.ok for c in sound.values()), [
        c.line() for c in sound.values() if not c.ok]
    for name in fails:
        got = controls[control][name]
        assert not got.ok, got.line()
        if factor:
            assert got.value > factor * sound[name].value
        elif name == "feature_vector_max_abs_diff":
            assert got.value >= 1.0        # a count off by one or more
    for name in holds:
        assert controls[control][name].ok, controls[control][name].line()


def test_bfloat16_does_not_move_the_hashed_block_and_the_output_says_so(
        checked):
    _, _, lines = checked
    (said,) = [ln for ln in lines if ln.startswith("bfloat16 moves")]
    assert "hashed block of" in said and "by 0.0:" in said


def test_a_program_without_the_text_spans_is_refused_at_once(extended,
                                                             monkeypatch):
    from transmogrifai_tpu.impl.feature import vectorizers
    root, m = extended
    cell = harness.load_cell(root, m, CELL)
    monkeypatch.delattr(vectorizers, "_tokenize_hash_counted")
    ctx = harness.Context(cell, 1, 0.0, False, harness.Monitor().install(),
                          lambda s: None)
    with pytest.raises(SystemExit, match="text.hash"):
        harness.loop_for(cell.traffic["kind"])(ctx)


def test_a_run_that_tokenised_every_row_in_python_is_not_correct(
        extended, monkeypatch):
    from transmogrifai_tpu.utils import text_native
    root, m = extended
    monkeypatch.setattr(text_native, "tokenize_hash_native",
                        lambda *a, **k: None)
    _, res, lines = _run(root, m)
    assert res["correct"] is False
    failed = [ln for ln in lines if ln.startswith("check ") and "FAILED" in ln]
    assert {ln.split(":")[0] for ln in failed} == {
        "check text_path_native", "check text_rows_native_off"}
    # the block itself is the same either way
    assert any(ln.startswith("check feature_vector_max_abs_diff: 0.0 ")
               for ln in lines)


def test_a_traced_run_reads_the_span_metrics_this_pr_adds(extended):
    root, m = extended
    cell, res, _ = _run(root, m, trace=True)
    got = res["metrics"]
    for name in TEXT:
        assert got[name]["value"] > 0, name
    # the host's steps that the shared readers give in this cell
    for name in HOST:
        assert got[name]["value"] > 0, name
    assert got["tx_fe_text_s"]["value"] >= (
        got["tx_fe_text_fit_s"]["value"] + got["tx_fe_text_hash_s"]["value"]
        + got["tx_fe_text_concat_s"]["value"])
    # the rows of the training table that are not ASCII, counted here
    gen = datagen_text.generate(cell.config, 2 ** 31 + 11,
                                cell.config["rows"]
                                + cell.config["holdout_rows"])
    docs = gen.columns["text"][:cell.config["rows"]]
    assert got["tx_fe_text_py_rows"]["value"] == sum(
        not d.isascii() for d in docs)
    # no device plane on the CPU: the device metrics say nothing
    assert not DEVICE & set(got)
