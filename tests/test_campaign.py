"""Chaos campaign engine (robustness/campaign.py + oracles.py;
docs/robustness.md "Chaos campaigns"): site-registry/docstring/docs
agreement and the no-dead-sites coverage guard, always-on fired-injection
accounting + the gated tg_chaos_injections_total counter, cross-process
kill detection via the run sentinel, the callable no-leak oracles, a
seeded multi-schedule campaign completing with 100% site coverage and
zero invariant violations, a deliberately planted recovery bug detected
and delta-debug minimized to a one-command TG_FAULTS reproducer, and the
two highest-risk pairwise interactions as named tests (preempt during a
downshifted stream; a failed drift refit racing an OOM flush split)."""
import json
import os
import re
import time

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.impl.preparators.sanity_checker import SanityChecker
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.local import micro_batch_score_function
from transmogrifai_tpu.manifest import SENTINEL_FILE, RunSentinel
from transmogrifai_tpu.observability import metrics as obs_metrics
from transmogrifai_tpu.robustness import faults, oracles
from transmogrifai_tpu.robustness.campaign import (
    ACCOUNT_KINDS, ChaosCampaign,
)
from transmogrifai_tpu.robustness.faults import (
    ALL_SITES, SimulatedPreemption, sites_for_scenario,
)
from transmogrifai_tpu.serving import ModelRegistry, ServeConfig, ServingRuntime
from transmogrifai_tpu.serving.drift import DriftConfig, live_refits
from transmogrifai_tpu.streaming import TableChunkSource
from transmogrifai_tpu.table import Column, FeatureTable
from transmogrifai_tpu.types import Real, RealNN
from transmogrifai_tpu.workflow import OpWorkflow

pytestmark = pytest.mark.campaign

PKG_ROOT = os.path.dirname(tg.__file__)
TESTS_DIR = os.path.dirname(__file__)


def _train_model(n=300, seed=7):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.randn(n), rng.randn(n)
    y = ((x1 + 0.5 * x2) > 0).astype(float)
    df = pd.DataFrame({"x1": x1, "x2": x2, "y": y})
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
             for c in ("x1", "x2")]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=seed,
        models=[("OpLogisticRegression",
                 [{"regParam": 0.01, "elasticNetParam": 0.0}])])
        .set_input(label, checked).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred).train())


@pytest.fixture(scope="module")
def model():
    return _train_model()


# ---------------------------------------------------------------------------
# Site registry: machine-readable inventory, three-way agreement, no dead
# sites
# ---------------------------------------------------------------------------

def test_registry_shape():
    assert len(ALL_SITES) >= 24
    for name, spec in ALL_SITES.items():
        assert spec.name == name
        assert spec.modes and set(spec.modes) <= {"raise", "nan",
                                                  "preempt", "oom"}
        assert spec.scenarios and spec.recovery
    # canonical (first) scenario of every site is a real harness
    eng_scenarios = {c.name for c in ChaosCampaign._SCENARIOS}
    canon = {s.scenarios[0] for s in ALL_SITES.values()}
    assert canon <= eng_scenarios | {"mesh_sweep"}


def test_registry_agrees_with_faults_docstring():
    """The docstring tables in faults.py and the registry must list the
    same sites — the inventory cannot silently rot."""
    doc_sites = set(re.findall(r"^``([a-z_]+\.[a-z_]+)``", faults.__doc__,
                               re.MULTILINE))
    assert doc_sites == set(ALL_SITES), (
        f"docstring-only: {sorted(doc_sites - set(ALL_SITES))}; "
        f"registry-only: {sorted(set(ALL_SITES) - doc_sites)}")


def test_registry_agrees_with_docs_robustness_md():
    docs = open(os.path.join(PKG_ROOT, "..", "docs",
                             "robustness.md")).read()
    table_sites = set(re.findall(r"^\| `([a-z_]+\.[a-z_]+)` \|", docs,
                                 re.MULTILINE))
    assert table_sites == set(ALL_SITES), (
        f"docs-only: {sorted(table_sites - set(ALL_SITES))}; "
        f"registry-only: {sorted(set(ALL_SITES) - table_sites)}")


def test_registry_modules_compile_their_sites():
    """Every registered site's owning module really compiles the site
    name in (an inject/poison call or the site-string default) — the
    registry can never point at code that no longer exists."""
    for name, spec in sorted(ALL_SITES.items()):
        path = os.path.join(PKG_ROOT, spec.module.replace("/", os.sep))
        assert os.path.isfile(path), f"{name}: module {spec.module} gone"
        src = open(path).read()
        assert f'"{name}"' in src, (
            f"site {name} not found in its registered module "
            f"{spec.module}")


def test_no_dead_chaos_sites_every_site_armed_by_tier1_tests():
    """The coverage guard: (a) the campaign's coverage pass provably arms
    every registered site in THIS tier-1 suite, and (b) every site is
    also named literally by at least one test module — a site nobody can
    arm is dead weight in production code."""
    eng = ChaosCampaign(seed=0)
    try:
        scheds = eng.generate(len(ALL_SITES), ensure_coverage=True)
    finally:
        eng.close()
    armed = {s for sch in scheds for s in sch["faults"]}
    assert armed == set(ALL_SITES), (
        f"coverage pass misses: {sorted(set(ALL_SITES) - armed)}")
    blob = "".join(
        open(os.path.join(TESTS_DIR, f)).read()
        for f in sorted(os.listdir(TESTS_DIR)) if f.endswith(".py"))
    missing = [s for s in sorted(ALL_SITES) if s not in blob]
    assert not missing, f"sites never named by any test: {missing}"


# ---------------------------------------------------------------------------
# Injection observability: fired counts + tg_chaos_injections_total
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_fired_counts_and_injection_counter():
    obs_metrics.enable_metrics(True)
    try:
        with faults.injected({
                "dag.stage_fit": {"mode": "raise", "nth": 2, "count": 1},
                "validator.fold_metrics": {"mode": "nan", "nth": 1}}):
            faults.inject("dag.stage_fit")          # call 1: no fire
            with pytest.raises(faults.TransientFaultError):
                faults.inject("dag.stage_fit")      # call 2: fires
            faults.inject("dag.stage_fit")          # call 3: window past
            out = faults.poison("validator.fold_metrics",
                                np.ones(3))         # fires
            assert np.isnan(out[0])
            assert faults.fired_counts() == {
                "dag.stage_fit": {"raise": 1},
                "validator.fold_metrics": {"nan": 1}}
            snap = obs_metrics.registry().snapshot()
            series = snap["tg_chaos_injections_total"]
            assert series["mode=raise,site=dag.stage_fit"] == 1.0
            assert series["mode=nan,site=validator.fold_metrics"] == 1.0
        assert faults.fired_counts() == {}          # cleared on disarm
    finally:
        obs_metrics.enable_metrics(None)
        from transmogrifai_tpu import observability
        observability.reset()


@pytest.mark.chaos
def test_injection_counter_zero_writes_when_metrics_off():
    with faults.injected({"dag.stage_fit": {"mode": "raise", "nth": 1}}):
        with pytest.raises(faults.TransientFaultError):
            faults.inject("dag.stage_fit")
        # process-local accounting always on; the metric is gated
        assert faults.fired_counts()["dag.stage_fit"]["raise"] == 1
        assert not obs_metrics.registry().snapshot()


# ---------------------------------------------------------------------------
# Cross-process kill detection: the run sentinel
# ---------------------------------------------------------------------------

def test_run_sentinel_lifecycle(tmp_path):
    s = RunSentinel(str(tmp_path))
    s.start("dag_fit")
    doc = RunSentinel.read(str(tmp_path))
    assert doc == {"pid": os.getpid(), "phase": "dag_fit"}
    assert s.read_stale() is None                  # own pid: not stale
    s.set_phase("device_dispatch")
    assert RunSentinel.read(str(tmp_path))["phase"] == "device_dispatch"
    assert RunSentinel.suspects_oom_kill(RunSentinel.read(str(tmp_path)))
    assert not RunSentinel.suspects_oom_kill({"phase": "checkpoint_write"})
    s.clear()
    assert RunSentinel.read(str(tmp_path)) is None


def _ckpt_workflow(df, ckpt_dir, seed=9):
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
             for c in ("x1", "x2")]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=seed,
        models=[("OpLogisticRegression",
                 [{"regParam": 0.01, "elasticNetParam": 0.0}])])
        .set_input(label, checked).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred).with_checkpoint_dir(ckpt_dir))


def test_unclean_exit_recorded_on_resume(tmp_path):
    """A stale sentinel from a DIFFERENT process (the cross-process
    OOM-kill / SIGKILL case) surfaces as summary()["faults"]
    ["uncleanExits"], with oomKillSuspected when the last phase was
    device work; the resume itself proceeds normally."""
    rng = np.random.RandomState(3)
    df = pd.DataFrame({"x1": rng.randn(200), "x2": rng.randn(200)})
    df["y"] = ((df.x1 + df.x2) > 0).astype(float)
    ckpt = str(tmp_path / "ckpt")
    clean = _ckpt_workflow(df, ckpt).train()
    assert not os.path.exists(os.path.join(ckpt, SENTINEL_FILE))
    assert clean.summary()["faults"]["uncleanExits"] == []
    # forge the dying breath of another process killed mid-upload
    from transmogrifai_tpu.manifest import atomic_write_json
    atomic_write_json(os.path.join(ckpt, SENTINEL_FILE),
                      {"pid": 999_999_999, "phase": "device_upload"})
    resumed = _ckpt_workflow(df, ckpt).train(resume=True)
    exits = resumed.summary()["faults"]["uncleanExits"]
    assert len(exits) == 1
    assert exits[0]["kind"] == "unclean_exit"
    assert exits[0]["detail"]["pid"] == 999_999_999
    assert exits[0]["detail"]["oomKillSuspected"] is True
    # this run exited cleanly: its own sentinel is gone again
    assert not os.path.exists(os.path.join(ckpt, SENTINEL_FILE))
    # non-device phases are an unclean exit but not an OOM suspect
    atomic_write_json(os.path.join(ckpt, SENTINEL_FILE),
                      {"pid": 999_999_998, "phase": "checkpoint_write"})
    again = _ckpt_workflow(df, ckpt).train(resume=True)
    detail = again.summary()["faults"]["uncleanExits"][0]["detail"]
    assert detail["oomKillSuspected"] is False


@pytest.mark.chaos
def test_preemption_leaves_sentinel_same_process_resume_not_flagged(
        tmp_path):
    """An in-process simulated kill leaves the sentinel behind (the
    evidence a REAL kill would leave), but a same-pid resume is not
    flagged — in-process recovery is already accounted by the preemption
    machinery; the sentinel exists for cross-process deaths."""
    rng = np.random.RandomState(4)
    df = pd.DataFrame({"x1": rng.randn(200), "x2": rng.randn(200)})
    df["y"] = ((df.x1 - df.x2) > 0).astype(float)
    ckpt = str(tmp_path / "ckpt")
    with faults.injected({"preempt.stage_fit":
                          {"mode": "preempt", "nth": 1}}):
        with pytest.raises(SimulatedPreemption):
            _ckpt_workflow(df, ckpt).train()
        assert os.path.exists(os.path.join(ckpt, SENTINEL_FILE))
        resumed = _ckpt_workflow(df, ckpt).train(resume=True)
    assert resumed.summary()["faults"]["uncleanExits"] == []
    assert not os.path.exists(os.path.join(ckpt, SENTINEL_FILE))


# ---------------------------------------------------------------------------
# Callable oracles
# ---------------------------------------------------------------------------

def test_oracles_clean_process_reports_nothing():
    assert oracles.campaign_violations() == []


def test_oracles_detect_and_clean_a_leaked_runtime(model):
    rt = ServingRuntime(model, "leaky",
                        ServeConfig(max_batch=4, max_queue=8))
    assert "leaky" in oracles.leaked_serving_runtimes()
    problems = oracles.campaign_violations()
    assert any("serving runtime" in p for p in problems)
    # the sweep force-closed the leak so the next schedule starts clean
    assert not oracles.leaked_serving_runtimes()
    assert rt.health_state() == "stopped"
    assert oracles.campaign_violations() == []


# ---------------------------------------------------------------------------
# Engine: generation, the seeded campaign, minimization
# ---------------------------------------------------------------------------

def test_generate_is_deterministic_and_covering():
    e1 = ChaosCampaign(seed=21)
    e2 = ChaosCampaign(seed=21)
    e3 = ChaosCampaign(seed=22)
    try:
        g1, g2 = e1.generate(40), e2.generate(40)
        assert g1 == g2                      # same seed, same schedules
        assert g1 != e3.generate(40)         # a different seed differs
        covered = {s for sch in g1[:len(ALL_SITES)]
                   for s in sch["faults"]}
        assert covered == set(ALL_SITES)
        for sch in g1:
            assert sch["scenario"] in e1.scenarios
            pool = set(sites_for_scenario(sch["scenario"]))
            assert set(sch["faults"]) <= pool
            for site, spec in sch["faults"].items():
                assert spec["mode"] in ALL_SITES[site].modes
    finally:
        e1.close(), e2.close(), e3.close()


@pytest.mark.chaos
def test_seeded_campaign_full_coverage_zero_violations():
    """The headline acceptance path at tier-1 scale: a seeded campaign
    over every registered site (coverage singletons + randomized
    multi-site schedules) completes deterministically with 100% site
    coverage, zero invariant violations, and full serve accounting. A
    longer soak is ``op campaign --schedules 200``."""
    eng = ChaosCampaign(seed=7)
    try:
        report = eng.run(count=len(ALL_SITES) + 4)
        doc = report.to_json()
        assert report.ok, doc["violations"]
        assert doc["uncovered"] == [], doc["firedBySite"]
        assert doc["coveragePct"] == 100.0
        acct = doc["accounting"]
        assert acct["lost"] == 0 and acct["failed"] == 0
        # caller-cancelled requests are a TYPED shed bucket, part of the
        # identity — never silently vanished (serve scenarios cancel one)
        assert acct["submitted"] == (acct["completed"] + acct["shed"]
                                     + acct["cancelled"])
        assert acct["cancelled"] > 0
        # outcome taxonomy: every schedule either completed or raised a
        # documented typed error (the typed-error-discipline oracle
        # would have flagged anything else)
        for res in doc["results"]:
            assert (res["outcome"] == "completed"
                    or res["outcome"].startswith("raised:")), res
    finally:
        eng.close()


@pytest.mark.chaos
def test_planted_recovery_bug_detected_minimized_and_reproduced(
        monkeypatch):
    """The acceptance criterion for minimization: a deliberately planted
    recovery bug (the degraded eager path drops one record — a lost
    request) is caught by the accounting oracle, delta-debugged to a
    <=2-site schedule, and its emitted TG_FAULTS reproducer re-triggers
    the violation — then passes once the bug is fixed."""
    from transmogrifai_tpu.serving import runtime as srt
    orig = srt.ServingRuntime._eager_records

    def buggy(self, reqs):
        out = orig(self, reqs)
        return out[:-1] if len(out) > 1 else out

    eng = ChaosCampaign(seed=5, collect_timeout=1.5)
    try:
        schedule = {"scenario": "serve", "faults": {
            "serve.flush": {"mode": "raise", "nth": 1, "count": 1},
            "drift.fold": {"mode": "raise", "nth": 1, "count": 1},
            "serve.enqueue": {"mode": "raise", "nth": 2, "count": 1}}}
        monkeypatch.setattr(srt.ServingRuntime, "_eager_records", buggy)
        res = eng.run_schedule(schedule)
        assert any("lost" in v for v in res["violations"]), res
        minimized = eng.minimize(schedule)
        assert len(minimized) <= 2, minimized
        assert "serve.flush" in minimized   # the site that routes the
        #                                     flush onto the buggy path
        repro = eng.reproducer("serve", minimized)
        assert json.loads(repro["env"]["TG_FAULTS"]) == minimized
        assert "TG_CHAOS=1" in repro["cmd"]
        assert "cli campaign --scenario serve" in repro["cmd"]
        assert eng.run_repro(repro)["violations"], (
            "reproducer failed to re-trigger the planted bug")
        monkeypatch.setattr(srt.ServingRuntime, "_eager_records", orig)
        assert not eng.run_repro(repro)["violations"], (
            "fixed build still violates the reproducer")
    finally:
        eng.close()


@pytest.mark.chaos
def test_cli_campaign_repro_mode_runs_single_schedule():
    from transmogrifai_tpu import cli
    res = cli.run_campaign(
        scenario="transfer",
        faults_json='{"distributed.device_put": {"mode": "raise",'
                    ' "nth": 1}}')
    assert res["outcome"] == "completed"
    assert res["fired"] == {"distributed.device_put": {"raise": 1}}
    assert res["violations"] == []


def test_account_kinds_reference_registered_sites():
    assert set(ACCOUNT_KINDS) <= set(ALL_SITES)


# ---------------------------------------------------------------------------
# Named pairwise interactions (the highest-risk compositions, pinned as
# tier-1 tests beyond the randomized campaigns)
# ---------------------------------------------------------------------------

def _stream_table(n=1600, d=4, seed=31):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    mask = rng.rand(n, d) >= 0.05
    y = (np.where(mask, X, 0.0)[:, 0] > 0.3).astype(np.float32)
    cols = {f"x{i}": Column(Real, X[:, i], mask[:, i]) for i in range(d)}
    cols["y"] = Column(RealNN, y, None)
    return FeatureTable(cols, n)


def _stream_pipeline(d=4):
    from transmogrifai_tpu.streaming import StreamingGBT
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(f"x{i}").extract_field().as_predictor()
             for i in range(d)]
    checked = label.transform_with(SanityChecker(seed=1),
                                   tg.transmogrify(feats))
    return (StreamingGBT(problem="binary", num_trees=1, max_depth=2,
                         n_bins=8, learning_rate=1.0)
            .set_input(label, checked).get_output())


def _rv_fills(m):
    rv = [s for s in m.stages
          if type(s).__name__ == "RealVectorizerModel"][0]
    return np.asarray(rv.fills)


def _preds(m, table):
    scored = m.score(table=table.drop(["y"]))
    return np.asarray(scored[m.result_features[0].name].values,
                      dtype=np.float64)


@pytest.mark.chaos
@pytest.mark.stream
def test_preempt_stage_fit_during_oom_downshifted_stream_resumes_bit_exact(
        tmp_path):
    """Pairwise: ``preempt.stage_fit`` kills the train AFTER an
    ``oom.stream`` downshift halved the chunk budget. The resume must
    restore the downshifted stage bit-exactly (checkpoint records carry
    the active chunkRows) and the final model must be bit-equal to the
    un-preempted downshifted run."""
    table = _stream_table()

    def make_wf(ckpt):
        # ONE workflow object per checkpoint dir: resume must see the
        # same stage uids a re-run script would regenerate (fresh builds
        # in-process mint fresh uids and would never match checkpoints)
        return (OpWorkflow().set_result_features(_stream_pipeline())
                .with_checkpoint_dir(ckpt))

    def train(wf, resume=False):
        return wf.train(stream=TableChunkSource(table, chunk_rows=400),
                        resume=resume)

    # reference: the downshift alone, uninterrupted
    with faults.injected({"oom.stream": {"mode": "oom", "nth": 2}}):
        ref = train(make_wf(str(tmp_path / "ref")))
    assert ref.summary()["faults"]["oomDownshifts"], "no downshift fired"

    # same downshift, then a kill at the SECOND stage's fit; the armed
    # context spans kill + resume so call counters carry across — the
    # downshift does not re-fire on resume, exactly like a real kill
    ckpt = str(tmp_path / "killed")
    wf = make_wf(ckpt)
    with faults.injected({
            "oom.stream": {"mode": "oom", "nth": 2},
            "preempt.stage_fit": {"mode": "preempt", "nth": 2}}):
        with pytest.raises(SimulatedPreemption):
            train(wf)
        assert os.path.exists(os.path.join(ckpt, SENTINEL_FILE))
        resumed = train(wf, resume=True)

    assert np.array_equal(_rv_fills(resumed), _rv_fills(ref))
    assert np.array_equal(_preds(resumed, table), _preds(ref, table))
    resume_info = resumed.summary()["resume"]
    assert resume_info["restoredStages"], (
        "the downshifted stage should restore from its checkpoint")
    assert not os.path.exists(os.path.join(ckpt, SENTINEL_FILE))


@pytest.mark.chaos
@pytest.mark.drift
def test_drift_refit_failure_with_oom_serve_split_keeps_old_model_serving(
        tmp_path, model):
    """Pairwise: ``drift.refit`` fails while ``oom.serve`` splits a
    flush underneath. The old model must keep serving with ZERO failed
    requests (bit-equal records), the refit failure must be typed, and
    the breaker must stay untouched by both faults."""
    saved = str(tmp_path / "m")
    model.save(saved)
    rng = np.random.RandomState(44)
    shifted = [{"x1": float(rng.randn() + 6.0),
                "x2": float(rng.randn() + 6.0)} for _ in range(128)]
    expect = micro_batch_score_function(model)(shifted)
    hook_calls = []

    def hook(name, rt, report):
        hook_calls.append(name)
        return saved

    cfg = ServeConfig(max_batch=32, max_queue=512, max_wait_ms=1.0)
    with faults.injected({
            "drift.refit": {"mode": "raise", "nth": 1},
            "oom.serve": {"mode": "oom", "nth": 1}}):
        with ModelRegistry(cfg, refit_hook=hook) as reg:
            rt = reg.load("m", saved)
            assert rt.drift_monitor is not None
            rt.drift_monitor.config = DriftConfig(min_rows=32,
                                                  every_rows=32)
            futs = [rt.submit(r) for r in shifted]
            recs = [f.result(timeout=60) for f in futs]
            t0 = time.monotonic()
            while live_refits() and time.monotonic() - t0 < 60:
                time.sleep(0.05)
            assert not live_refits()
            # the failed refit never swapped: the OLD runtime serves on
            assert reg.runtime("m") is rt
            kinds = {r.kind for r in rt.fault_log.reports}
            health = reg.health()
            breaker = rt.breaker.snapshot()
    assert recs == expect                       # zero failed, bit-equal
    assert "drift_refit_failed" in kinds
    assert "oom_downshift" in kinds
    assert not hook_calls                       # injected before the hook
    assert health["refits"] and health["refits"][0]["ok"] is False
    assert breaker["opens"] == 0 and breaker["state"] == "closed"


# ---------------------------------------------------------------------------
# Singleton coverage for the two sites no other test file armed literally
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_selector_refit_fault_falls_back_to_next_candidate():
    """``selector.refit``: the winner's refit raises — the next-ranked
    finite candidate refits instead and the quarantine is accounted."""
    rng = np.random.RandomState(17)
    n = 240
    df = pd.DataFrame({"x1": rng.randn(n), "x2": rng.randn(n)})
    df["y"] = ((df.x1 + df.x2) > 0).astype(float)
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
             for c in ("x1", "x2")]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=17,
        models=[("OpLogisticRegression",
                 [{"regParam": 0.01, "elasticNetParam": 0.0},
                  {"regParam": 0.3, "elasticNetParam": 0.5}])])
        .set_input(label, checked).get_output())
    with faults.injected({"selector.refit":
                          {"mode": "raise", "nth": 1}}):
        m = (OpWorkflow().set_input_dataset(df)
             .set_result_features(pred).train())
    quarantined = m.summary()["faults"]["quarantined"]
    assert any(q["site"] == "selector.refit" for q in quarantined)


@pytest.mark.chaos
def test_distributed_device_put_retries_transient_faults():
    """``distributed.device_put``: a transient placement fault is
    retried by the always-on default policy, bit-exactly."""
    from transmogrifai_tpu.parallel.distributed import (
        fetch_to_host, retrying_device_put)
    x = np.arange(512, dtype=np.float32)
    from transmogrifai_tpu.robustness.policy import FaultLog
    log = FaultLog()
    with log.activate():
        with faults.injected({"distributed.device_put":
                              {"mode": "raise", "nth": 1, "count": 2}}):
            dev = retrying_device_put(x)
        back = fetch_to_host(dev)
    assert np.array_equal(back, x)
    assert log.of_kind("retry")


# ---------------------------------------------------------------------------
# loadgen: full request accounting under open-loop load
# ---------------------------------------------------------------------------

@pytest.mark.serve
def test_loadgen_accounting_zero_lost(model):
    from transmogrifai_tpu.serving.loadgen import (
        run_open_loop, synthetic_rows)
    rows = synthetic_rows(model, 64, seed=2)
    cfg = ServeConfig(max_batch=32, max_queue=64, max_wait_ms=2.0)
    with ServingRuntime(model, "acct", cfg) as rt:
        rep = run_open_loop(rt, rows, seconds=0.4, rps=400.0)
    assert rep["accountingOk"], rep
    assert rep["lost"] == 0 and rep["failed"] == 0
    assert rep["offered"] == (rep["completed"] + rep["shedOverload"]
                              + rep["shedDeadline"] + rep["submitErrors"])
