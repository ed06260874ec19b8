"""Fault-isolated execution: quarantine, retry policies, checkpoint
resilience, and the deterministic fault-injection harness
(transmogrifai_tpu/robustness/; docs/robustness.md).

Every chaos test drives a REAL recovery path through an injected fault —
deterministic (call counters, not clocks), CPU-only, seeds pinned.
"""
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
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.robustness.guards import (
    AllCandidatesFailedError, params_finite, quarantine_non_finite,
)
from transmogrifai_tpu.robustness.policy import (
    FaultLog, FaultReport, RetryPolicy, is_transient_error,
)
from transmogrifai_tpu.workflow import OpWorkflow

LR_GRID = [{"regParam": 0.01, "elasticNetParam": 0.0},
           {"regParam": 0.1, "elasticNetParam": 0.0}]


def _df(n=300, seed=7):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.randn(n), rng.randn(n)
    y = ((x1 + 0.5 * x2) > 0).astype(float)
    return pd.DataFrame({"x1": x1, "x2": x2, "y": y})


def _pred(grid=None, models=None):
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    f1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    f2 = FeatureBuilder.Real("x2").extract_field().as_predictor()
    checked = tg.transmogrify([f1, f2]).sanity_check(label)
    models = models or [("OpLogisticRegression", grid or LR_GRID)]
    return (BinaryClassificationModelSelector.with_cross_validation(
        models=models).set_input(label, checked).get_output())


# ---------------------------------------------------------------------------
# RetryPolicy / FaultLog units
# ---------------------------------------------------------------------------

def test_retry_policy_fail_twice_then_succeed():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise faults.TransientFaultError("flaky")
        return "ok"

    log = FaultLog()
    with log.activate():
        out = RetryPolicy(max_retries=3, base_delay=0.0).execute(
            flaky, site="unit")
    assert out == "ok" and calls["n"] == 3
    (rep,) = log.of_kind("retry")
    assert rep.site == "unit" and rep.attempts == 3 and rep.retries == 2


def test_retry_policy_fatal_not_retried():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("deterministic bug")

    log = FaultLog()
    with log.activate():
        with pytest.raises(ValueError):
            RetryPolicy(max_retries=5, base_delay=0.0).execute(bad, site="u")
    assert calls["n"] == 1
    assert log.of_kind("fatal")


def test_retry_policy_exhaustion_raises():
    def always():
        raise faults.TransientFaultError("down")

    with pytest.raises(faults.TransientFaultError):
        RetryPolicy(max_retries=2, base_delay=0.0).execute(always, site="u")


def test_retry_policy_deterministic_backoff():
    p = RetryPolicy(base_delay=0.1, max_delay=10.0, jitter=0.25)
    d1 = [p.delay_for(a, "siteA") for a in range(3)]
    d2 = [p.delay_for(a, "siteA") for a in range(3)]
    assert d1 == d2                       # reproducible
    assert d1[0] < d1[1] < d1[2]          # exponential
    assert p.delay_for(0, "siteB") != d1[0]  # decorrelated across sites


def test_transient_classification():
    assert is_transient_error(faults.TransientFaultError("x"))
    assert is_transient_error(ConnectionResetError("reset"))
    assert is_transient_error(RuntimeError("UNAVAILABLE: socket closed"))
    assert not is_transient_error(ValueError("shape mismatch"))
    assert not is_transient_error(faults.InjectedFaultError("fatal"))


def test_fault_log_inactive_record_is_noop():
    FaultLog.record(FaultReport(site="s", kind="retry"))  # must not raise
    log = FaultLog()
    assert log.to_json() == {"quarantined": [], "retries": [],
                             "checkpointsSkipped": [], "restored": [],
                             "planFallbacks": [], "breakerDegraded": [],
                             "drift": [], "oomDownshifts": [],
                             "threadStalls": [], "uncleanExits": [],
                             "fatal": [], "droppedReports": 0}


# ---------------------------------------------------------------------------
# Guards units
# ---------------------------------------------------------------------------

def test_quarantine_non_finite_masks_and_records():
    fm = np.array([[0.9, np.nan, 0.8], [0.7, 0.5, np.inf]])
    grid = [{"a": 1}, {"a": 2}, {"a": 3}]
    mean, masked, recs = quarantine_non_finite("fam", grid, fm, "AuPR", True)
    assert np.isnan(mean[1]) and not np.isfinite(mean[2])
    assert masked[1] == -np.inf and masked[2] == -np.inf
    assert [r["gridIndex"] for r in recs] == [1, 2]
    assert int(np.argmax(masked)) == 0
    # all-finite passes the identical array through (bit-identical path)
    fm2 = np.array([[0.9, 0.8]])
    mean2, masked2, recs2 = quarantine_non_finite("fam", grid[:2], fm2,
                                                  "AuPR", True)
    assert recs2 == [] and masked2 is mean2


def test_params_finite():
    assert params_finite({"coef": np.array([1.0, 2.0]),
                          "nested": {"b": np.array([0.0])},
                          "ints": np.array([1, 2], dtype=np.int32)})
    assert not params_finite({"coef": np.array([1.0, np.nan])})
    assert not params_finite({"nested": {"b": np.array([np.inf])}})


def test_params_finite_inf_sentinel_allowed():
    """Tree thresholds carry +inf as the stopped-node sentinel
    (ModelFamily.inf_ok_params): exempt from the inf check, never from NaN."""
    p = {"thresh": np.array([np.inf, 1.0]), "leaf": np.array([0.5])}
    assert params_finite(p, allow_inf=("thresh",))
    assert not params_finite(p)
    assert not params_finite({"thresh": np.array([np.nan])},
                             allow_inf=("thresh",))
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    import transmogrifai_tpu.models.trees  # noqa: F401
    assert "thresh" in MODEL_REGISTRY["OpGBTClassifier"].inf_ok_params


# ---------------------------------------------------------------------------
# Fault-injection harness
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_injector_counts_and_clears():
    with faults.injected({"site.x": {"mode": "raise", "nth": 2, "count": 1}}):
        faults.inject("site.x")               # call 1: inert
        with pytest.raises(faults.TransientFaultError):
            faults.inject("site.x")           # call 2: fires
        faults.inject("site.x")               # call 3: inert again
        assert faults.active_sites() == ["site.x"]
    assert faults.active_sites() == []


@pytest.mark.chaos
def test_injector_key_filter_and_poison():
    with faults.injected({"p": {"mode": "nan", "key": "only", "index": None}}):
        a = np.ones(3)
        assert faults.poison("p", a, key="other") is a
        out = faults.poison("p", a, key="only")
        assert np.isnan(out).all() and np.isfinite(a).all()


def test_env_spec_ignored_without_chaos_gate(monkeypatch):
    monkeypatch.delenv(faults.CHAOS_ENV, raising=False)
    monkeypatch.setenv(faults.SPEC_ENV, '{"x": {"mode": "raise"}}')
    monkeypatch.setattr(faults, "_ENV_LOADED", False)
    assert faults.active_sites() == []
    monkeypatch.setattr(faults, "_ENV_LOADED", True)


# ---------------------------------------------------------------------------
# Quarantine end to end
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_nan_candidate_quarantined_sweep_completes():
    df = _df()
    with faults.injected({"validator.fold_metrics": {
            "mode": "nan", "index": 1, "key": "OpLogisticRegression"}}):
        pred = _pred()
        model = (OpWorkflow().set_input_dataset(df)
                 .set_result_features(pred).train())
    s = model.summary()
    sel = s[pred.origin_stage.uid]
    # winner is the surviving finite-metric candidate
    assert sel["bestHyperparameters"] == LR_GRID[0]
    assert np.isfinite(sel["bestMetricValue"])
    # exactly the poisoned candidate is quarantined, with its reason
    (q,) = s["faults"]["quarantined"]
    assert q["detail"]["family"] == "OpLogisticRegression"
    assert q["detail"]["gridIndex"] == 1
    assert q["detail"]["hyper"] == LR_GRID[1]
    assert "non-finite" in q["detail"]["reason"]
    assert sel["quarantinedCandidates"][0]["gridIndex"] == 1
    # the model still scores
    scored = model.score(df=df)
    assert pred.name in scored.column_names


@pytest.mark.chaos
def test_family_fit_throw_quarantines_family_not_sweep():
    df = _df()
    with faults.injected({"validator.family_fit": {
            "mode": "raise", "key": "OpLinearSVC", "count": 99}}):
        pred = _pred(models=[("OpLogisticRegression", LR_GRID),
                             ("OpLinearSVC", [{"regParam": 0.01}])])
        model = (OpWorkflow().set_input_dataset(df)
                 .set_result_features(pred).train())
    s = model.summary()
    sel = s[pred.origin_stage.uid]
    assert sel["bestModelType"] == "OpLogisticRegression"
    qs = s["faults"]["quarantined"]
    assert qs and all(r["detail"]["family"] == "OpLinearSVC" for r in qs)
    assert all("fit raised" in r["detail"]["reason"] for r in qs)


@pytest.mark.chaos
def test_all_candidates_failed_raises_aggregated():
    df = _df()
    with faults.injected({"validator.fold_metrics": {
            "mode": "nan", "index": None}}):
        pred = _pred()
        with pytest.raises(AllCandidatesFailedError) as ei:
            (OpWorkflow().set_input_dataset(df)
             .set_result_features(pred).train())
    # every candidate appears in the aggregated error
    assert len(ei.value.records) == len(LR_GRID)
    assert "all 2 sweep candidate(s) were quarantined" in str(ei.value)


@pytest.mark.chaos
def test_workflow_cv_quarantine():
    """The leakage-free workflow-CV path quarantines through the merged
    fold selection too."""
    df = _df(n=400)
    with faults.injected({"validator.fold_metrics": {
            "mode": "nan", "index": 1, "key": "OpLogisticRegression"}}):
        pred = _pred()
        model = (OpWorkflow().set_input_dataset(df)
                 .set_result_features(pred).with_workflow_cv().train())
    sel = model.summary()[pred.origin_stage.uid]
    assert sel["bestHyperparameters"] == LR_GRID[0]
    assert np.isfinite(sel["bestMetricValue"])
    assert any(r["gridIndex"] == 1 for r in sel["quarantinedCandidates"])


# ---------------------------------------------------------------------------
# Retry end to end
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_transient_transfer_retried_two_attempts():
    df = _df()
    with faults.injected({"distributed.to_host": {
            "mode": "raise", "nth": 1, "count": 2}}):
        pred = _pred()
        model = (OpWorkflow().set_input_dataset(df)
                 .set_result_features(pred).with_fault_policy().train())
    retries = model.summary()["faults"]["retries"]
    (rep,) = [r for r in retries if r["site"] == "distributed.to_host"]
    assert rep["retries"] == 2 and rep["attempts"] == 3
    assert model.summary()["faults"]["quarantined"] == []


@pytest.mark.chaos
def test_stage_fit_transient_error_retried_under_policy():
    df = _df()
    with faults.injected({"dag.stage_fit": {"mode": "raise", "nth": 1}}):
        pred = _pred()
        model = (OpWorkflow().set_input_dataset(df)
                 .set_result_features(pred)
                 .with_fault_policy(RetryPolicy(max_retries=2,
                                                base_delay=0.0))
                 .train())
    retries = model.summary()["faults"]["retries"]
    assert any(r["site"].startswith("dag.stage_fit") and r["retries"] == 1
               for r in retries)


@pytest.mark.chaos
def test_stage_fit_fatal_without_policy():
    """Without with_fault_policy the injected transient error propagates —
    retries are opt-in, guards are not."""
    df = _df()
    with faults.injected({"dag.stage_fit": {"mode": "raise", "nth": 1}}):
        pred = _pred()
        with pytest.raises(faults.TransientFaultError):
            (OpWorkflow().set_input_dataset(df)
             .set_result_features(pred).train())


# ---------------------------------------------------------------------------
# Checkpoint resilience
# ---------------------------------------------------------------------------

def test_corrupt_checkpoint_skipped_and_reported(tmp_path):
    df = _df(n=250)
    ck = str(tmp_path / "ckpt")

    reset_uids()
    m1 = (OpWorkflow().set_input_dataset(df)
          .set_result_features(_pred()).with_checkpoint_dir(ck).train())
    npzs = sorted(f for f in os.listdir(ck) if f.endswith(".npz"))
    assert npzs
    # truncate one stage's arrays — a crash mid-write / torn copy
    with open(os.path.join(ck, npzs[0]), "wb") as fh:
        fh.write(b"not-an-npz")

    reset_uids()
    m2 = (OpWorkflow().set_input_dataset(df)
          .set_result_features(_pred()).with_checkpoint_dir(ck).train())
    skipped = m2.summary()["faults"]["checkpointsSkipped"]
    (rep,) = skipped
    assert rep["detail"]["uid"] == npzs[0][:-4]
    assert "error" in rep["detail"]
    # resumed training still converges to the same scores
    p1 = m1.result_features[0].name
    p2 = m2.result_features[0].name
    np.testing.assert_allclose(
        np.asarray(m1.score(df=df)[p1].values),
        np.asarray(m2.score(df=df)[p2].values), atol=1e-5)


# ---------------------------------------------------------------------------
# No-fault parity + satellites
# ---------------------------------------------------------------------------

def test_no_injection_bit_identical_selection():
    """With no faults armed, the guarded sweep must select identically and
    report an empty faults section."""
    df = _df()
    reset_uids()
    m1 = (OpWorkflow().set_input_dataset(df)
          .set_result_features(_pred()).train())
    reset_uids()
    m2 = (OpWorkflow().set_input_dataset(df)
          .set_result_features(_pred()).train())
    s1 = [v for k, v in m1.summary().items() if k != "faults"
          and "bestMetricValue" in v]
    s2 = [v for k, v in m2.summary().items() if k != "faults"
          and "bestMetricValue" in v]
    assert s1[0]["bestHyperparameters"] == s2[0]["bestHyperparameters"]
    assert s1[0]["bestMetricValue"] == s2[0]["bestMetricValue"]
    f = m1.summary()["faults"]
    assert f["quarantined"] == [] and f["retries"] == []
    assert f["checkpointsSkipped"] == [] and f["fatal"] == []


def test_fused_cache_lru_bounded(monkeypatch):
    from transmogrifai_tpu.impl.tuning import validators as V
    monkeypatch.setattr(V, "_FUSED_CACHE_MAX", 4)
    V._FUSED_CACHE.clear()
    for i in range(10):
        V._fused_cache_put(("key", i), object())
    assert len(V._FUSED_CACHE) == 4
    # LRU: a get refreshes recency
    assert V._fused_cache_get(("key", 6)) is not None
    V._fused_cache_put(("key", 99), object())
    assert V._fused_cache_get(("key", 6)) is not None   # kept (recent)
    assert V._fused_cache_get(("key", 7)) is None        # evicted (oldest)
    V._FUSED_CACHE.clear()


def test_ensemble_cap_proportional_scaling(caplog):
    import logging

    from transmogrifai_tpu.models import trees
    # uniform grids keep the plain clamp
    np.testing.assert_array_equal(
        trees._sweep_ensemble_cap(np.array([50.0, 50.0]), 16, "numTrees"),
        [16.0, 16.0])
    # below-cap grids are untouched
    assert trees._sweep_ensemble_cap(np.array([4.0, 8.0]), 16, "t") is None
    # distinct above-cap values scale proportionally and warn
    with caplog.at_level(logging.WARNING,
                         logger="transmogrifai_tpu.models.trees"):
        out = trees._sweep_ensemble_cap(np.array([8.0, 64.0]), 16, "numTrees")
    np.testing.assert_array_equal(out, [2.0, 16.0])
    assert any("proportionally scaled" in r.message for r in caplog.records)
    # scaled candidates stay distinguishable — the failure mode the uniform
    # clamp had (byte-identical fits → selection by grid order)
    assert out[0] != out[1]


def test_sweep_sampling_defaults_and_explicit_eval_rows():
    """The sweep's sampling constants are the values every ledger line was
    taken with; an explicit ``max_eval_rows`` is kept as given."""
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models import trees

    assert OpCrossValidation().max_eval_rows == 32768
    assert trees._SWEEP_HIST_SAMPLE == 8192
    assert trees._SWEEP_RF_TREES == 16
    assert trees._SWEEP_GBT_ROUNDS == 12
    assert OpCrossValidation(max_eval_rows=1000).max_eval_rows == 1000
    assert OpCrossValidation(max_eval_rows=None).max_eval_rows is None
