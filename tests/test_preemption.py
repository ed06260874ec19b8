"""Preemption-safe training: atomic checkpoints + integrity manifest +
resumable sweeps (transmogrifai_tpu/manifest.py, persistence.py,
impl/tuning/sweep_checkpoint.py; docs/robustness.md "Preemption safety").

The chaos tests kill ``train()`` at each named preemption site with a
deterministic :class:`SimulatedPreemption` (a BaseException — no recovery
path may swallow it, like a real SIGTERM), then assert that
``train(resume=True)`` completes and reproduces the uninterrupted run's
selected candidate and evaluation metrics.
"""
import json
import os

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.features import reset_uids
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.manifest import (
    CheckpointManifest, atomic_write_bytes, clean_tmp_debris, sha256_bytes,
)
from transmogrifai_tpu.impl.tuning.sweep_checkpoint import (
    SweepCheckpoint, candidate_key, params_hash,
)
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.robustness.faults import SimulatedPreemption
from transmogrifai_tpu.robustness.policy import FaultLog
from transmogrifai_tpu.workflow import OpWorkflow

LR_GRID = [{"regParam": 0.01, "elasticNetParam": 0.0},
           {"regParam": 0.1, "elasticNetParam": 0.0}]
MODELS = [("OpLogisticRegression", LR_GRID),
          ("OpLinearSVC", [{"regParam": 0.01}])]


def _df(n=300, seed=7):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.randn(n), rng.randn(n)
    y = ((x1 + 0.5 * x2) > 0).astype(float)
    return pd.DataFrame({"x1": x1, "x2": x2, "y": y})


def _pred():
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    f1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    f2 = FeatureBuilder.Real("x2").extract_field().as_predictor()
    checked = tg.transmogrify([f1, f2]).sanity_check(label)
    return (BinaryClassificationModelSelector.with_cross_validation(
        models=MODELS).set_input(label, checked).get_output())


def _selector_summary(model):
    return next(v for k, v in model.summary().items()
                if k != "faults" and isinstance(v, dict)
                and "bestModelType" in v)


def _baseline(df):
    reset_uids()
    pred = _pred()
    model = (OpWorkflow().set_input_dataset(df)
             .set_result_features(pred).train())
    return model, pred


def _assert_same_outcome(df, base_model, base_pred, model, pred):
    b, r = _selector_summary(base_model), _selector_summary(model)
    assert r["bestModelType"] == b["bestModelType"]
    assert r["bestHyperparameters"] == b["bestHyperparameters"]
    assert r["bestMetricValue"] == b["bestMetricValue"]
    for section in ("trainEvaluation", "holdoutEvaluation"):
        assert set(r[section]) == set(b[section])
        for k in b[section]:
            np.testing.assert_allclose(r[section][k], b[section][k],
                                       rtol=1e-6, atol=1e-7, err_msg=k)
    np.testing.assert_allclose(
        np.asarray(model.score(df=df)[pred.name].values),
        np.asarray(base_model.score(df=df)[base_pred.name].values),
        atol=1e-6)


# ---------------------------------------------------------------------------
# Kill-at-site → resume → identical outcome (the tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.parametrize("site,spec", [
    ("preempt.stage_fit", {"mode": "preempt", "nth": 2}),
    ("preempt.checkpoint_write", {"mode": "preempt", "nth": 1}),
    ("preempt.sweep", {"mode": "preempt", "nth": 2}),
    ("preempt.refit", {"mode": "preempt", "nth": 1}),
])
def test_preempt_then_resume_matches_uninterrupted(tmp_path, site, spec):
    df = _df()
    base_model, base_pred = _baseline(df)

    ck = str(tmp_path / "ckpt")
    reset_uids()
    pred1 = _pred()
    with faults.injected({site: spec}):
        with pytest.raises(SimulatedPreemption):
            (OpWorkflow().set_input_dataset(df).set_result_features(pred1)
             .with_checkpoint_dir(ck).train())

    # fresh process re-executes the same script: uids reproduce
    reset_uids()
    pred2 = _pred()
    model = (OpWorkflow().set_input_dataset(df).set_result_features(pred2)
             .with_checkpoint_dir(ck).train(resume=True))
    _assert_same_outcome(df, base_model, base_pred, model, pred2)

    res = model.summary()["resume"]
    assert res["requested"] is True
    if site == "preempt.stage_fit":
        # the first estimator completed + checkpointed before the kill
        assert res["restoredStages"]
    if site == "preempt.checkpoint_write":
        # the kill landed INSIDE the first checkpoint write: nothing was
        # committed, and the torn write is reported, never used
        assert res["restoredStages"] == []
        skipped = model.summary()["faults"]["checkpointsSkipped"]
        assert any("manifest" in r["detail"]["reason"] for r in skipped)
    if site == "preempt.sweep":
        # the first family's candidates were persisted before the kill
        fams = [r["family"] for r in res["restoredSweepCandidates"]]
        assert "OpLogisticRegression" in fams
    if site == "preempt.refit":
        # the whole sweep survived: every family replays from disk
        fams = {r["family"] for r in res["restoredSweepCandidates"]}
        assert fams == {"OpLogisticRegression", "OpLinearSVC"}
        # upstream stages restored too (prep stages checkpointed in run 1)
        assert res["restoredStages"]


@pytest.mark.chaos
def test_double_preemption_then_resume(tmp_path):
    """Two successive kills at different depths still converge: each resume
    extends the durable prefix (stage checkpoints, then sweep state)."""
    df = _df()
    base_model, base_pred = _baseline(df)
    ck = str(tmp_path / "ckpt")

    for site, spec in [("preempt.stage_fit", {"mode": "preempt", "nth": 2}),
                       ("preempt.refit", {"mode": "preempt", "nth": 1})]:
        reset_uids()
        p = _pred()
        with faults.injected({site: spec}):
            with pytest.raises(SimulatedPreemption):
                (OpWorkflow().set_input_dataset(df).set_result_features(p)
                 .with_checkpoint_dir(ck).train(resume=True))

    reset_uids()
    pred = _pred()
    model = (OpWorkflow().set_input_dataset(df).set_result_features(pred)
             .with_checkpoint_dir(ck).train(resume=True))
    _assert_same_outcome(df, base_model, base_pred, model, pred)
    assert model.summary()["resume"]["restoredStages"]


def test_resume_without_checkpoint_dir_raises():
    df = _df()
    reset_uids()
    pred = _pred()
    with pytest.raises(ValueError, match="with_checkpoint_dir"):
        (OpWorkflow().set_input_dataset(df)
         .set_result_features(pred).train(resume=True))


# ---------------------------------------------------------------------------
# Integrity manifest: corruption is detected and reported, never used
# ---------------------------------------------------------------------------

def test_checkpoint_dir_has_manifest_and_checksums(tmp_path):
    df = _df(n=250)
    ck = str(tmp_path / "ckpt")
    reset_uids()
    (OpWorkflow().set_input_dataset(df).set_result_features(_pred())
     .with_checkpoint_dir(ck).train())
    mpath = os.path.join(ck, "MANIFEST.json")
    assert os.path.isfile(mpath)
    with open(mpath) as fh:
        doc = json.load(fh)
    assert doc["manifestVersion"] == 1 and doc["stages"]
    # every recorded file verifies; no tmp debris left behind
    m, err = CheckpointManifest.load(ck, 1)
    assert err is None
    for fname in m.files:
        assert m.verify_file(fname) is None, fname
    assert not [f for f in os.listdir(ck) if f.endswith(".tmp")]
    # the selector's sweep state was persisted and committed
    assert m.sweeps


def test_bad_checksum_detected_and_surfaced(tmp_path):
    """Flip bytes INSIDE a checkpoint file keeping its size: only a content
    hash can catch this — and it must surface in summary()['faults']."""
    df = _df(n=250)
    ck = str(tmp_path / "ckpt")
    reset_uids()
    m1 = (OpWorkflow().set_input_dataset(df).set_result_features(_pred())
          .with_checkpoint_dir(ck).train())
    npzs = sorted(f for f in os.listdir(ck) if f.endswith(".npz"))
    target = os.path.join(ck, npzs[0])
    data = bytearray(open(target, "rb").read())
    data[len(data) // 2] ^= 0xFF
    with open(target, "wb") as fh:
        fh.write(bytes(data))

    reset_uids()
    pred2 = _pred()
    m2 = (OpWorkflow().set_input_dataset(df).set_result_features(pred2)
          .with_checkpoint_dir(ck).train(resume=True))
    skipped = m2.summary()["faults"]["checkpointsSkipped"]
    (rep,) = [r for r in skipped if r["detail"]["uid"] == npzs[0][:-4]]
    assert "sha256 mismatch" in rep["detail"]["reason"]
    assert rep["detail"]["file"].endswith(npzs[0])
    # the poisoned stage refit; results still match
    assert npzs[0][:-4] not in m2.summary()["resume"]["restoredStages"]
    np.testing.assert_allclose(
        np.asarray(m1.score(df=df)[m1.result_features[0].name].values),
        np.asarray(m2.score(df=df)[pred2.name].values), atol=1e-5)


def test_truncated_file_detected(tmp_path):
    df = _df(n=250)
    ck = str(tmp_path / "ckpt")
    reset_uids()
    (OpWorkflow().set_input_dataset(df).set_result_features(_pred())
     .with_checkpoint_dir(ck).train())
    npzs = sorted(f for f in os.listdir(ck) if f.endswith(".npz"))
    target = os.path.join(ck, npzs[0])
    data = open(target, "rb").read()
    with open(target, "wb") as fh:
        fh.write(data[: len(data) // 2])

    reset_uids()
    m2 = (OpWorkflow().set_input_dataset(df).set_result_features(_pred())
          .with_checkpoint_dir(ck).train(resume=True))
    skipped = m2.summary()["faults"]["checkpointsSkipped"]
    (rep,) = [r for r in skipped if r["detail"]["uid"] == npzs[0][:-4]]
    assert "size mismatch" in rep["detail"]["reason"]


def test_manifest_unit_verify_and_debris(tmp_path):
    d = str(tmp_path / "dir")
    os.makedirs(d)
    sha = atomic_write_bytes(os.path.join(d, "a.bin"), b"hello")
    assert sha == sha256_bytes(b"hello")
    m = CheckpointManifest(d, 1)
    m.record_file("a.bin", sha, 5)
    m.complete_stage("st_1", ["a.bin"])
    m.save()
    m2, err = CheckpointManifest.load(d, 1)
    assert err is None and m2.verify_file("a.bin") is None
    assert m2.verify_file("missing.bin") is not None
    # unrecorded payload files are debris; tmp files are cleaned silently
    open(os.path.join(d, "orphan.npz"), "wb").write(b"x")
    open(os.path.join(d, "half.npz.tmp"), "wb").write(b"x")
    assert m2.unrecorded_files() == ["orphan.npz"]
    assert clean_tmp_debris(d) == ["half.npz.tmp"]
    # wrong format version refuses the whole dir
    _, err2 = CheckpointManifest.load(d, 2)
    assert err2 is not None and "format" in err2


# ---------------------------------------------------------------------------
# Sweep checkpoint units
# ---------------------------------------------------------------------------

def test_sweep_metrics_roundtrip_bit_exact():
    fm = np.array([[0.5, np.nan, np.inf], [-np.inf, 0.25, 1e-30]],
                  dtype=np.float32)
    rec = SweepCheckpoint.encode_metrics(fm)
    assert json.loads(json.dumps(rec))  # JSON-safe (no NaN literals needed)
    back = SweepCheckpoint.decode_metrics(json.loads(json.dumps(rec)))
    assert back.dtype == np.float32
    np.testing.assert_array_equal(back, fm)


def test_candidate_key_sensitivity():
    fp = {"n": 100, "F": 3, "yhash": "abc"}
    k = candidate_key("fam", LR_GRID, fp)
    assert k == candidate_key("fam", [dict(g) for g in LR_GRID], fp)
    assert k != candidate_key("fam2", LR_GRID, fp)
    assert k != candidate_key("fam", LR_GRID[:1], fp)
    assert k != candidate_key("fam", LR_GRID, dict(fp, yhash="zzz"))
    assert params_hash({"a": 1, "b": 2}) == params_hash({"b": 2, "a": 1})


def test_sweep_checkpoint_put_get_and_corruption(tmp_path):
    d = str(tmp_path / "ck")
    os.makedirs(d)
    ck = SweepCheckpoint(d, "sel_1")
    rec = {"family": "f", "grid": LR_GRID, "metricName": "AuPR",
           "paramsHashes": [params_hash(g) for g in LR_GRID],
           **SweepCheckpoint.encode_metrics(np.ones((3, 2), np.float32)),
           "quarantined": False, "reason": None}
    ck.put("k1", rec)
    # a fresh instance (new process) reads it back through the manifest
    ck2 = SweepCheckpoint(d, "sel_1")
    assert ck2.get("k1")["family"] == "f"
    assert ck2.get("nope") is None
    # corrupt the sweep file: the record is dropped, not decoded
    with open(ck.path, "wb") as fh:
        fh.write(b"garbage")
    ck3 = SweepCheckpoint(d, "sel_1")
    assert ck3.get("k1") is None


@pytest.mark.parametrize("reason", [None, "fit raised ValueError: boom"])
def test_what_persist_writes_is_what_restore_returns(tmp_path, reason):
    d = str(tmp_path / "ck")
    os.makedirs(d)
    fm = np.array([[0.5, np.nan], [np.inf, -np.inf], [0.25, 1e-30]],
                  dtype=np.float32)
    SweepCheckpoint(d, "sel_1").persist("k1", "fam", LR_GRID, "AuPR", fm,
                                        reason)
    ck = SweepCheckpoint(d, "sel_1")        # a new process
    with FaultLog().activate() as log:
        back, why = ck.restore("k1", 3, 2)
    assert back.dtype == np.float32 and why == reason
    np.testing.assert_array_equal(back, fm)
    (rep,) = log.reports
    assert (rep.site, rep.kind) == ("sweep.candidate", "restored")
    assert rep.detail == {
        "family": "fam", "configs": 2, "candidateKey": "k1",
        "quarantined": reason is not None}
    rec = ck.get("k1")
    assert rec["grid"] == LR_GRID and rec["metricName"] == "AuPR"
    assert rec["paramsHashes"] == [params_hash(g) for g in LR_GRID]
    # no record, or one of another sweep's folds or grid: nothing replays
    assert ck.restore("k2", 3, 2) is None
    assert ck.restore("k1", 2, 2) is None and ck.restore("k1", 3, 1) is None


# ---------------------------------------------------------------------------
# Atomic save_model + CorruptModelError (satellite)
# ---------------------------------------------------------------------------

def _small_model(df):
    reset_uids()
    pred = _pred()
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred).train()), pred


def test_save_model_atomic_with_manifest(tmp_path):
    from transmogrifai_tpu.workflow import OpWorkflowModel
    df = _df(n=250)
    model, pred = _small_model(df)
    path = str(tmp_path / "model")
    model.save(path)
    assert os.path.isfile(os.path.join(path, "MANIFEST.json"))
    assert not [f for f in os.listdir(path) if f.endswith(".tmp")]
    m, err = CheckpointManifest.load(path, 1)
    assert err is None
    assert m.verify_file("plan.json") is None
    assert m.verify_file("arrays.npz") is None
    loaded = OpWorkflowModel.load(path)
    np.testing.assert_allclose(
        np.asarray(model.score(df=df)[pred.name].values),
        np.asarray(loaded.score(df=df)[pred.name].values), atol=1e-6)


@pytest.mark.parametrize("victim", ["arrays.npz", "plan.json"])
def test_load_model_corruption_raises_descriptive(tmp_path, victim):
    from transmogrifai_tpu.persistence import CorruptModelError
    from transmogrifai_tpu.workflow import OpWorkflowModel
    df = _df(n=250)
    model, _ = _small_model(df)
    path = str(tmp_path / "model")
    model.save(path)
    target = os.path.join(path, victim)
    data = open(target, "rb").read()
    with open(target, "wb") as fh:
        fh.write(data[: len(data) // 2])
    with pytest.raises(CorruptModelError) as ei:
        OpWorkflowModel.load(path)
    assert victim in str(ei.value)
    assert ei.value.path.endswith(victim)
    assert "mismatch" in ei.value.reason


def test_load_model_without_manifest_still_wraps_decode_error(tmp_path):
    """Legacy dirs (no manifest) get the decode-error wrapping instead of a
    raw npz traceback."""
    from transmogrifai_tpu.persistence import CorruptModelError
    from transmogrifai_tpu.workflow import OpWorkflowModel
    df = _df(n=250)
    model, _ = _small_model(df)
    path = str(tmp_path / "model")
    model.save(path)
    os.remove(os.path.join(path, "MANIFEST.json"))
    with open(os.path.join(path, "arrays.npz"), "wb") as fh:
        fh.write(b"not an npz")
    with pytest.raises(CorruptModelError) as ei:
        OpWorkflowModel.load(path)
    assert "arrays.npz" in str(ei.value)


# ---------------------------------------------------------------------------
# Scoring-path schema guards (satellite)
# ---------------------------------------------------------------------------

def test_micro_batch_quarantines_bad_rows():
    from transmogrifai_tpu.local import (
        SCORE_ERROR_KEY, micro_batch_score_function,
    )
    df = _df()
    model, pred = _small_model(df)
    score = micro_batch_score_function(model)
    rows = df.to_dict("records")
    clean = score(rows[:4])
    bad = dict(rows[1], x1="definitely-not-a-number")
    mixed = score([rows[0], bad, rows[2], rows[3]])
    assert SCORE_ERROR_KEY in mixed[1]
    assert mixed[1][pred.name] is None
    assert "x1" in mixed[1][SCORE_ERROR_KEY]
    # the valid rows still score, identically to the clean batch
    for i in (0, 2, 3):
        assert SCORE_ERROR_KEY not in mixed[i]
        assert mixed[i][pred.name]["prediction"] == pytest.approx(
            clean[i][pred.name]["prediction"], abs=1e-6)


def test_compiled_score_missing_column_raises_schema_error():
    from transmogrifai_tpu.local import ScoreSchemaError
    from transmogrifai_tpu.local.scoring import compiled_score_function
    from transmogrifai_tpu.readers.readers import dataframe_to_table
    df = _df()
    model, _ = _small_model(df)
    score = compiled_score_function(model)
    table = dataframe_to_table(df, model.raw_features)
    bad = table.select([n for n in table.column_names if n != "x1"])
    with pytest.raises((ScoreSchemaError, ValueError), match="x1"):
        score(bad)
