"""Replica fleet + front door (serving/fleet.py, serving/frontdoor.py;
docs/serving.md "Replica fleet & front door").

The contract under test is ROADMAP item 2's hard invariant: a front
door over N shared-nothing replicas survives replica loss with ZERO
lost requests — every accepted future resolves exactly once, a record
bit-equal to the single-process run or a *typed* shed, across
load-aware routing, probe ejection/readmission, mid-flight kills,
rolling deploys, pre-dispatch admission control and autoscaling.
"""
import time

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.local import micro_batch_score_function
from transmogrifai_tpu.observability import devicemem
from transmogrifai_tpu.observability import postmortem as pm
from transmogrifai_tpu.observability import slo as slo_mod
from transmogrifai_tpu.observability import timeseries as ts_mod
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.robustness.campaign import ChaosCampaign
from transmogrifai_tpu.robustness.faults import ALL_SITES
from transmogrifai_tpu.serving import (
    AdmissionRefusedError, FleetConfig, FrontDoor, OverloadError,
    ServeConfig,
)
from transmogrifai_tpu.serving.fleet import ReplicaLostError
from transmogrifai_tpu.serving.loadgen import run_open_loop, synthetic_rows
from transmogrifai_tpu.workflow import OpWorkflow

pytestmark = pytest.mark.fleet


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


@pytest.fixture(scope="module")
def saved(model, tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fleet_model") / "m")
    model.save(d)
    return d


def _rows(model, n=24, seed=57):
    return synthetic_rows(model, n, seed=seed)


def _cfg(**kw):
    """Slow-flush default: requests sit queued for up to 500ms, so
    queue depths (and mid-flight kills) are deterministic."""
    base = dict(max_batch=64, max_queue=256, max_wait_ms=500.0)
    base.update(kw)
    return ServeConfig(**base)


def _fc(**kw):
    """Manual probing + no autoscale unless a test opts in."""
    base = dict(min_replicas=1, max_replicas=4, probe_interval_ms=0.0,
                probe_failures=3, readmit_probes=2, max_failovers=2,
                autoscale=False)
    base.update(kw)
    return FleetConfig(**base)


def _fleet(model, replicas=2, cfg=None, fc=None, **kw):
    return FrontDoor({"m": model}, replicas=replicas,
                     config=cfg or _cfg(), fleet_config=fc or _fc(), **kw)


# ---------------------------------------------------------------------------
# Site registry
# ---------------------------------------------------------------------------

def test_fleet_sites_registered():
    for site in ("fleet.route", "fleet.replica_kill", "fleet.probe"):
        spec = ALL_SITES[site]
        assert "fleet" in spec.scenarios
        assert spec.modes == ("raise",)
        assert spec.module == "serving/frontdoor.py"
        assert spec.bit_equal  # every fleet recovery is bit-preserving


# ---------------------------------------------------------------------------
# Load-aware routing
# ---------------------------------------------------------------------------

def test_routing_prefers_shallow_queues(model):
    rows = _rows(model, 12)
    with _fleet(model, replicas=2) as fd:
        r0 = fd._replicas["r0"]
        # pre-load r0 directly (bypassing the router): its queue is now
        # 6 deep while r1 is empty — the slow flush keeps it that way
        staged = [r0.submit("m", r) for r in rows[:6]]
        routed = [fd.submit(r) for r in rows[6:]]
        dist = fd.replica_distribution()
        assert dist["r1"] == 6 and dist["r0"] == 0, (
            f"router sent traffic to the deep queue: {dist}")
        for f in staged + routed:
            assert f.result(timeout=15) is not None


def test_routing_balances_empty_queues(model):
    rows = _rows(model, 16)
    with _fleet(model, replicas=2) as fd:
        futs = [fd.submit(r) for r in rows]
        dist = fd.replica_distribution()
        # live queue depths alternate the pick deterministically
        assert dist == {"r0": 8, "r1": 8}
        recs = [f.result(timeout=15) for f in futs]
        assert recs == micro_batch_score_function(model)(list(rows))


# ---------------------------------------------------------------------------
# Mid-flight replica loss: zero lost futures, bit-equal records
# ---------------------------------------------------------------------------

def test_replica_kill_mid_flight_zero_lost_bit_equal(
        model, tmp_path, monkeypatch):
    monkeypatch.setenv("TG_POSTMORTEM_DIR", str(tmp_path / "pm"))
    rows = _rows(model, 24)
    baseline = micro_batch_score_function(model)(list(rows))
    with _fleet(model, replicas=2) as fd:
        futs = [fd.submit(r) for r in rows]  # queued on both (slow flush)
        dist = fd.replica_distribution()
        assert dist["r0"] == 12 and dist["r1"] == 12
        fd.kill_replica("r0")
        # every future resolves — the 12 queued on r0 failed over to r1
        recs = [f.result(timeout=20) for f in futs]
        assert recs == baseline
        snap = fd.fleet_snapshot()
        assert snap["kills"] == 1
        assert snap["failovers"] >= 12
        assert fd.replica_distribution()["r1"] == 24
        kinds = {r.kind for r in fd.fault_log.reports}
        assert "replica_lost" in kinds and "fleet_failover" in kinds
        # a retried request must not double-count as completed
        assert fd.summary()["rowsScored"] == 24.0
    # the kill dumped ONE schema-valid replica_lost post-mortem bundle
    bundles = pm.list_bundles(str(tmp_path / "pm"))
    docs = [pm.read_bundle(p) for p in bundles]
    assert [d["trigger"]["kind"] for d in docs] == ["replica_lost"]
    assert not pm.validate_bundle(docs[0])
    assert docs[0]["trigger"]["detail"]["replica"] == "r0"


@pytest.mark.chaos
def test_replica_kill_chaos_site_typed_accounting(model):
    """``fleet.replica_kill`` armed: the routed-to replica dies at the
    routing hop; the request (and everything queued) fails over with
    full typed accounting."""
    rows = _rows(model, 12)
    baseline = micro_batch_score_function(model)(list(rows))
    with faults.injected({"fleet.replica_kill":
                          {"mode": "raise", "nth": 1, "count": 1}}):
        with _fleet(model, replicas=2) as fd:
            futs = [fd.submit(r) for r in rows]
            recs = [f.result(timeout=20) for f in futs]
            assert recs == baseline
            snap = fd.fleet_snapshot()
            assert snap["kills"] == 1
            states = {r.rid: r.state for r in fd._replicas.values()}
            assert list(states.values()).count("dead") == 1


@pytest.mark.chaos
def test_route_chaos_fails_over_bit_equal(model):
    rows = _rows(model, 8)
    baseline = micro_batch_score_function(model)(list(rows))
    with faults.injected({"fleet.route":
                          {"mode": "raise", "nth": 1, "count": 2}}):
        with _fleet(model, replicas=2) as fd:
            futs = [fd.submit(r) for r in rows]
            recs = [f.result(timeout=15) for f in futs]
            assert recs == baseline
            assert fd.fleet_snapshot()["failovers"] == 2
            kinds = [r.kind for r in fd.fault_log.reports]
            assert kinds.count("fleet_failover") == 2


def test_no_healthy_replica_sheds_typed_pre_dispatch(model):
    rows = _rows(model, 4)
    with _fleet(model, replicas=2) as fd:
        scorer_calls = []
        for rep in fd._replicas.values():
            rt = rep.registry.runtime("m")
            orig = rt._scorer
            rt._scorer = (lambda rs, _o=orig:
                          (scorer_calls.append(len(rs)) or _o(rs)))
        fd.kill_replica("r0")
        fd.kill_replica("r1")
        for r in rows:
            with pytest.raises(OverloadError):
                fd.submit(r)
        assert scorer_calls == []  # shed at the door, no dispatch
        snap = fd.fleet_snapshot()
        assert snap["sheds"]["no_replica"] == 4.0


def test_failover_budget_exhausts_typed(model):
    """A request that keeps losing replicas sheds typed after the
    bounded failover budget — never an untyped error, never a hang."""
    rows = _rows(model, 2)
    with faults.injected({"fleet.route":
                          {"mode": "raise", "nth": 1, "count": 99}}):
        with _fleet(model, replicas=2,
                    fc=_fc(max_failovers=2)) as fd:
            with pytest.raises(OverloadError):
                fd.submit(rows[0])
            # 3 attempts = initial + 2 failovers, then the typed shed
            assert fd.fleet_snapshot()["failovers"] == 3
            assert fd.fleet_snapshot()["sheds"]["no_replica"] == 1.0
    faults.clear()


# ---------------------------------------------------------------------------
# Probe ladder: ejection + readmission
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_ejection_and_readmission_ladder(model):
    rows = _rows(model, 6)
    with _fleet(model, replicas=2,
                fc=_fc(probe_failures=2, readmit_probes=2)) as fd:
        with faults.injected({"fleet.probe":
                              {"mode": "raise", "nth": 1, "count": 2,
                               "key": "r0"}}):
            fd.probe_now()
            assert fd._replicas["r0"].state == "active"  # 1 of 2
            fd.probe_now()
            assert fd._replicas["r0"].state == "ejected"
        kinds = [r.kind for r in fd.fault_log.reports]
        assert kinds.count("fleet_probe_failed") == 2
        assert "fleet_ejected" in kinds
        # ejected replicas take no new traffic
        futs = [fd.submit(r) for r in rows]
        assert fd.replica_distribution() == {"r0": 0, "r1": 6}
        [f.result(timeout=15) for f in futs]
        # the readmission half: consecutive healthy probes
        fd.probe_now()
        assert fd._replicas["r0"].state == "ejected"  # 1 of 2
        fd.probe_now()
        assert fd._replicas["r0"].state == "active"
        assert "fleet_readmitted" in {r.kind for r in fd.fault_log.reports}
        snap = fd.fleet_snapshot()
        assert snap["ejections"] == 1 and snap["readmissions"] == 1


def test_degraded_readiness_ejects_immediately(model):
    """A replica whose breaker is open (device path failing / watchdog
    stall trips it) reports un-ready and is ejected on the next probe —
    no failure-count ladder for a replica that SAYS it is sick."""
    with _fleet(model, replicas=2) as fd:
        rt = fd._replicas["r0"].registry.runtime("m")
        rt.breaker.trip(error=RuntimeError("staged device failure"))
        fd.probe_now()
        assert fd._replicas["r0"].state == "ejected"
        reasons = [r.detail.get("reason", "")
                   for r in fd.fault_log.of_kind("fleet_ejected")]
        assert any("degraded readiness" in r for r in reasons)


# ---------------------------------------------------------------------------
# Rolling deploy
# ---------------------------------------------------------------------------

def test_rolling_deploy_zero_loss(model, saved):
    rows = _rows(model, 24)
    baseline = micro_batch_score_function(model)(list(rows))
    with _fleet(model, replicas=2) as fd:
        before = [fd.submit(r) for r in rows[:12]]
        report = fd.deploy(saved)
        assert [r["ok"] for r in report] == [True, True]
        after = [fd.submit(r) for r in rows[12:]]
        recs = ([f.result(timeout=20) for f in before]
                + [f.result(timeout=20) for f in after])
        assert recs == baseline  # zero loss, zero sheds, bit-equal
        snap = fd.fleet_snapshot()
        assert snap["sheds"] == {"overload": 0.0, "deadline": 0.0,
                                 "admission": 0.0, "no_replica": 0.0,
                                 "placement": 0.0, "unknown_model": 0.0}
        assert snap["counts"] == {"active": 2}
        assert fd.deploy_history[-1]["ok"]
        # future autoscale spawns come up on the deployed artifact
        assert fd.models["m"] == saved


# ---------------------------------------------------------------------------
# Pre-flight admission control (the PR 9 remainder)
# ---------------------------------------------------------------------------

def test_admission_refusal_typed_and_pre_dispatch(model):
    """Predicted flush bytes over TG_DEVICE_BUDGET even at the minimum
    bucket: every request refuses typed AT THE DOOR — the scorer spy
    proves no dispatch ever happened (refuse, not catch-and-bisect)."""
    devicemem.record_cost("seg0", 256, 10 ** 9)  # 1GB per 256-row flush
    with _fleet(model, replicas=1,
                fc=_fc(device_budget=10 ** 6)) as fd:
        plan = fd._admission
        assert plan["refused"] and plan["estBytes"] == 10 ** 9
        rt = fd._replicas["r0"].registry.runtime("m")
        scorer_calls = []
        orig = rt._scorer
        rt._scorer = (lambda rs, _o=orig:
                      (scorer_calls.append(len(rs)) or _o(rs)))
        for r in _rows(model, 4):
            with pytest.raises(AdmissionRefusedError):
                fd.submit(r)
        assert scorer_calls == []
        snap = fd.fleet_snapshot()
        assert snap["sheds"]["admission"] == 4.0
        assert not fd.health()["ready"]  # refusing everything ≠ ready


def test_admission_split_lowers_flush_bucket(model):
    """Budget fits a 256-row flush but not the configured 1024: the
    fleet SPLITS — every replica's max_batch drops to the admitted
    bucket and requests keep serving (degrade, don't refuse)."""
    devicemem.record_cost("seg0", 256, 500)
    with _fleet(model, replicas=2, cfg=_cfg(max_batch=1024),
                fc=_fc(device_budget=600)) as fd:
        plan = fd._admission
        assert plan["split"] and plan["admittedRows"] == 256
        assert not plan["refused"]
        for rep in fd._replicas.values():
            assert rep.registry.runtime("m").config.max_batch == 256
        rec = fd.submit(_rows(model, 1)[0]).result(timeout=15)
        assert rec is not None
        assert "admission_split" in {r.kind for r in fd.fault_log.reports}


def test_admission_admits_without_cost_rows(model):
    """No measured cost rows (no warm, no MANIFEST costs) → admit:
    admission control consumes telemetry, it does not guess."""
    with _fleet(model, replicas=1, fc=_fc(device_budget=1)) as fd:
        assert fd._admission["basis"] == "no-cost-rows"
        assert fd.submit(_rows(model, 1)[0]).result(timeout=15)


# ---------------------------------------------------------------------------
# Front-door sheds burn the same SLO budgets (satellite: shed accounting)
# ---------------------------------------------------------------------------

def test_frontdoor_shed_moves_slo_burn_rate(model):
    """A front-door shed (no healthy replica) lands on the SAME
    tg_serve_shed_total series the runtime uses, so the SLO availability
    SLI — and tg_slo_burn_rate — must move on fleet-level sheds."""
    with _fleet(model, replicas=1) as fd:
        now = [0.0]
        sampler = ts_mod.MetricsSampler(fd.metrics, name="t",
                                        clock=lambda: now[0],
                                        every_s=0.1)
        sampler.tick()  # born-at-zero anchor
        tracker = slo_mod.SLOTracker(
            slo_mod.SLOSpec(model="m", window_s=720.0), sampler,
            fd.metrics, runtime=fd, clock=lambda: now[0])
        fd.kill_replica("r0")
        shed = 0
        for r in _rows(model, 10):
            with pytest.raises(OverloadError):
                fd.submit(r)
            shed += 1
        now[0] = 0.5
        sampler.tick()
        snap = tracker.evaluate(now=now[0])
        avail = snap["objectives"]["availability"]
        assert avail["badFraction"] == 1.0  # 10 sheds, 0 completions
        assert avail["burn"]["page"]["long"] >= 14.4
        assert avail["alerts"]["page"] is True
        gauges = fd.metrics.snapshot()["tg_slo_burn_rate"]
        assert gauges["model=m,slo=availability"] > 0.0


# ---------------------------------------------------------------------------
# Autoscale
# ---------------------------------------------------------------------------

def test_autoscale_up_down_from_staged_scale_hints(model):
    with _fleet(model, replicas=1,
                fc=_fc(min_replicas=1, max_replicas=3)) as fd:
        # staged "up" hints (what registry.health()["scaleHints"] would
        # carry under queue pressure / shed rate / a page alert)
        assert fd.autoscale_now(hints=["up"]) == "up"
        assert sorted(fd._replicas) == ["r0", "r1"]
        assert fd.autoscale_now(hints=["up", "hold"]) == "up"
        assert sorted(fd._replicas) == ["r0", "r1", "r2"]
        # at the ceiling: the decision stands but nothing spawns
        assert fd.autoscale_now(hints=["up"]) == "up"
        assert len([r for r in fd._replicas.values()
                    if r.state == "active"]) == 3
        # the new replica actually serves
        assert fd.submit(_rows(model, 1)[0]).result(timeout=15)
        # unanimous "down" retires (drains) back toward the floor
        assert fd.autoscale_now(hints=["down", "down", "down"]) == "down"
        states = {r.rid: r.state for r in fd._replicas.values()}
        assert states["r2"] == "retired"
        assert fd.autoscale_now(hints=["down", "down"]) == "down"
        assert fd.autoscale_now(hints=["down"]) == "down"  # at the floor
        active = [r for r in fd._replicas.values()
                  if r.state == "active"]
        assert len(active) == 1  # never below min_replicas
        assert [e["direction"] for e in fd.scale_events] == [
            "up", "up", "down", "down"]


def test_autoscale_from_cached_probe_hints(model):
    """The probe pass caches each replica's health scaleHints; the
    autoscale step consumes them with no explicit hints argument."""
    with _fleet(model, replicas=1,
                fc=_fc(min_replicas=1, max_replicas=2)) as fd:
        fd._replicas["r0"].probe.scale_hints = {"m": "up"}
        assert fd.autoscale_now() == "up"
        assert sorted(fd._replicas) == ["r0", "r1"]


# ---------------------------------------------------------------------------
# Loadgen integration + duck-typed surfaces
# ---------------------------------------------------------------------------

def test_loadgen_over_frontdoor_accounting_and_distribution(model):
    rows = _rows(model, 64)
    with _fleet(model, replicas=2,
                cfg=_cfg(max_wait_ms=2.0)) as fd:
        rep = run_open_loop(fd, rows, seconds=0.6, rps=400.0)
        assert rep["accountingOk"]
        assert rep["lost"] == 0 and rep["failed"] == 0
        assert rep["shedNoReplica"] == 0
        assert set(rep["replicas"]) == {"r0", "r1"}
        # clean run: every completion was routed exactly once
        assert sum(rep["replicas"].values()) == rep["completed"]
        assert rep["fleet"]["failovers"] == 0


def test_summary_and_health_shapes(model):
    with _fleet(model, replicas=2) as fd:
        fd.submit(_rows(model, 1)[0]).result(timeout=15)
        s = fd.summary()
        assert s["state"] == "ready" and s["rowsScored"] == 1.0
        assert s["scaleHint"]["hint"] in ("up", "hold", "down")
        assert set(s["shed"]) == {"overload", "deadline", "admission",
                                  "no_replica", "placement",
                                  "unknown_model"}
        h = fd.health()
        assert h["ready"]
        assert set(h["replicas"]) == {"r0", "r1"}
        assert all(v["ready"] for v in h["replicas"].values())
        fb = h["fleet"]
        assert fb["counts"] == {"active": 2}
        assert fb["admission"]["enabled"] is False


# ---------------------------------------------------------------------------
# Campaign scenario: the compositional accounting oracle
# ---------------------------------------------------------------------------

@pytest.mark.campaign
def test_fleet_campaign_scenario_clean_and_killed():
    eng = ChaosCampaign(seed=5, scenarios=["fleet"])
    try:
        clean = eng.run_schedule({"scenario": "fleet", "faults": {}})
        assert clean["outcome"] == "completed"
        assert not clean["violations"]
        killed = eng.run_schedule({"scenario": "fleet", "faults": {
            "fleet.replica_kill": {"mode": "raise", "nth": 1,
                                   "count": 1}}})
        assert killed["outcome"] == "completed"
        assert not killed["violations"], killed["violations"]
        assert killed["fired"] == {"fleet.replica_kill": {"raise": 1}}
        acct = killed["accounting"]
        assert acct["lost"] == 0 and acct["failed"] == 0
        assert acct["completed"] + acct["shed"] == acct["submitted"]
    finally:
        eng.close()


@pytest.mark.campaign
def test_fleet_campaign_multi_fault_schedule():
    """route + probe + kill together: the accounting identity must
    survive the composition, not just each site alone."""
    eng = ChaosCampaign(seed=6, scenarios=["fleet"])
    try:
        res = eng.run_schedule({"scenario": "fleet", "faults": {
            "fleet.route": {"mode": "raise", "nth": 1, "count": 1},
            "fleet.probe": {"mode": "raise", "nth": 1, "count": 1},
            "fleet.replica_kill": {"mode": "raise", "nth": 1,
                                   "count": 1}}})
        assert res["outcome"] == "completed"
        assert not res["violations"], res["violations"]
        assert set(res["fired"]) == {"fleet.route", "fleet.probe",
                                     "fleet.replica_kill"}
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Subprocess replicas (the multi-process soak arm; slow — spawns real
# OS processes with their own jax imports)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_subprocess_replica_round_trip_and_kill(model, saved):
    from transmogrifai_tpu.serving.fleet import SubprocessReplica
    rows = _rows(model, 6)
    baseline = micro_batch_score_function(model)(list(rows))
    rep = SubprocessReplica("r0", {"m": saved})
    try:
        futs = [rep.submit("m", r) for r in rows]
        recs = [f.result(timeout=60) for f in futs]
        assert recs == baseline  # bit-equal across the JSON pipe
        assert rep.health(timeout=30).get("ready")
    finally:
        rep.kill()
    with pytest.raises(ReplicaLostError):
        rep.submit("m", rows[0])


@pytest.mark.slow
def test_subprocess_fleet_kill_failover(model, saved):
    rows = _rows(model, 12)
    baseline = micro_batch_score_function(model)(list(rows))
    fc = _fc(subprocess=True, max_failovers=3)
    with FrontDoor({"m": saved}, replicas=2, config=_cfg(),
                   fleet_config=fc) as fd:
        assert {r.kind for r in fd._replicas.values()} == {"subprocess"}
        futs = [fd.submit(r) for r in rows]
        fd.kill_replica("r0")  # SIGKILL — a real process death
        recs = [f.result(timeout=60) for f in futs]
        assert recs == baseline
        assert fd.fleet_snapshot()["kills"] == 1


def test_subprocess_spawn_refused_while_parent_holds_the_chip(
        monkeypatch, tmp_path):
    """One chip serves one process: a parent that has initialised JAX on
    an accelerator gets a typed, immediate refusal — no worker is
    spawned, no 180 s spawn timeout (docs/serving.md)."""
    from transmogrifai_tpu.serving import fleet

    monkeypatch.setattr(fleet, "_parent_holds_accelerator", lambda: "tpu")
    spawned = []
    monkeypatch.setattr(fleet.subprocess, "Popen",
                        lambda *a, **k: spawned.append(a))
    with pytest.raises(fleet.ChipHeldError, match="already holds"):
        fleet.SubprocessReplica("r0", {"m": str(tmp_path)})
    assert not spawned
    # on the test harness itself nothing is held: the backend is the CPU
    monkeypatch.undo()
    assert fleet._parent_holds_accelerator() is None


def test_subprocess_worker_death_at_startup_fails_at_once(
        monkeypatch, tmp_path):
    """A worker that exits during start-up fails the spawn as soon as its
    pipe closes — not at ``spawn_timeout_s``."""
    from transmogrifai_tpu.serving import fleet

    monkeypatch.setattr(fleet.sys, "executable", "/bin/false")
    t0 = time.monotonic()
    with pytest.raises(ReplicaLostError, match="exited during start-up"):
        fleet.SubprocessReplica("r0", {"m": str(tmp_path)},
                                spawn_timeout_s=170.0)
    assert time.monotonic() - t0 < 60.0
