"""Always-on flight recorder + post-mortem bundles
(transmogrifai_tpu/observability/blackbox.py + postmortem.py;
docs/observability.md "Flight recorder & post-mortems"): ring bound +
drop counting, correlation-id propagation enqueue→resolve, ONE
schema-valid bundle per trigger class through the existing chaos sites
(serve.dispatch→breaker, oom.serve, drift verdict, watchdog stall,
unclean-exit sentinel), the dump rate limit, bundle schema round-trip,
``op doctor`` rendering, latency exemplars + loadgen slowest-K, the
campaign violation→bundle attach, Prometheus bucket exposition, and the
recorder overhead guard."""
import json
import os
import re
import time

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.local import micro_batch_score_function
from transmogrifai_tpu.manifest import SENTINEL_FILE, atomic_write_json
from transmogrifai_tpu.observability import blackbox as bb
from transmogrifai_tpu.observability import metrics as om
from transmogrifai_tpu.observability import postmortem as pm
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.robustness import watchdog as wd
from transmogrifai_tpu.serving import CircuitBreaker, ServeConfig, ServingRuntime
from transmogrifai_tpu.serving.drift import (
    DEGRADED, DriftBaseline, DriftConfig, DriftMonitor,
)
from transmogrifai_tpu.serving.loadgen import run_open_loop, synthetic_rows
from transmogrifai_tpu.workflow import OpWorkflow

pytestmark = pytest.mark.blackbox


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


def _rows(n, seed=3):
    rng = np.random.RandomState(seed)
    return [{"x1": float(rng.randn()), "x2": float(rng.randn())}
            for _ in range(n)]


def _cfg(**kw):
    base = dict(max_batch=8, max_queue=64, max_wait_ms=2.0)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture
def bundles(tmp_path, monkeypatch):
    """Point TG_POSTMORTEM_DIR at a per-test directory and return a
    callable listing its (validated-on-read) bundle docs."""
    d = str(tmp_path / "postmortems")
    monkeypatch.setenv("TG_POSTMORTEM_DIR", d)

    def docs():
        return [(p, pm.read_bundle(p)) for p in pm.list_bundles(d)]

    return docs


# ---------------------------------------------------------------------------
# Ring semantics
# ---------------------------------------------------------------------------

def test_ring_bound_and_drop_counting():
    rec = bb.FlightRecorder(max_events=8)
    for i in range(12):
        rec.record("e", i=i)
    events = rec.events()
    assert len(events) == 8
    assert rec.dropped == 4
    # newest events win: the oldest 4 were evicted
    assert [e.attrs["i"] for e in events] == list(range(4, 12))
    snap = rec.snapshot()
    assert snap["events"] == 8 and snap["dropped"] == 4
    rec.clear()
    assert rec.events() == [] and rec.dropped == 0


def test_disabled_recorder_writes_nothing():
    bb.enable_blackbox(False)
    try:
        before = len(bb.recorder().events())
        bb.record("should.not.appear", x=1)
        assert len(bb.recorder().events()) == before
        assert pm.trigger("breaker_open", detail={}) is None
    finally:
        bb.enable_blackbox(None)


def test_correlated_scope_stamps_events():
    corr = bb.new_correlation_id("run")
    with bb.correlated(corr):
        bb.record("inside")
    bb.record("outside")
    kinds = {e.kind: e.corr for e in bb.recorder().events()}
    assert kinds["inside"] == corr
    assert kinds["outside"] is None
    assert [e.kind for e in bb.recorder().slice_for(corr)] == ["inside"]


# ---------------------------------------------------------------------------
# Correlation-id propagation through the serving runtime
# ---------------------------------------------------------------------------

def test_corr_propagates_enqueue_to_resolve_single_flush(model):
    """Each submitted request carries one bit-stable correlation id from
    enqueue to resolve: the Future exposes it, and the recorder slice for
    that id replays the request's timeline across ONE coalesced flush."""
    rows = _rows(4)
    rt = ServingRuntime(model, "corr", _cfg(), auto_start=False)
    try:
        futs = [rt.submit(r) for r in rows]
        corrs = [f.tg_corr for f in futs]
        assert all(isinstance(c, str) and c.startswith("req-")
                   for c in corrs)
        assert len(set(corrs)) == 4  # unique per request
        rt.start()
        recs = [f.result(timeout=30) for f in futs]
        assert all(r is not None for r in recs)
    finally:
        rt.close()
    snap = rt.metrics.snapshot()
    assert snap["tg_serve_batch_rows"]["model=corr"]["count"] == 1, \
        "staged queue must coalesce into a single flush"
    for corr in corrs:
        kinds = [e.kind for e in bb.recorder().slice_for(corr)]
        assert kinds.count("serve.enqueue") == 1, kinds
        assert kinds.count("serve.resolve") == 1, kinds
        assert kinds.index("serve.enqueue") < kinds.index("serve.resolve")
    # the same ids resurface in the latency histogram's slowest-K
    # exemplars — a p99 outlier names its request
    hist = rt.metrics.histogram("tg_serve_request_seconds", model="corr")
    exemplars = {x["exemplar"] for x in hist.exemplars()}
    assert exemplars and exemplars <= set(corrs)


def test_train_run_gets_correlation_and_timeline():
    model = _train_model(n=200, seed=11)
    corr = model._correlation
    assert corr is not None and corr.startswith("run-")
    kinds = [e.kind for e in bb.recorder().slice_for(corr)]
    assert "workflow.train" in kinds and "workflow.train_done" in kinds
    assert "sweep.family" in kinds  # the selector sweep is stamped too


# ---------------------------------------------------------------------------
# One schema-valid bundle per trigger class (existing chaos sites)
# ---------------------------------------------------------------------------

def _assert_single_valid_bundle(docs, kind):
    assert len(docs) == 1, (
        f"expected exactly one bundle, got {[p for p, _ in docs]}")
    path, doc = docs[0]
    assert kind in os.path.basename(path)
    problems = pm.validate_bundle(doc)
    assert not problems, problems
    assert doc["trigger"]["kind"] == kind
    # the triggering event must be visible in the ring slice
    ring_kinds = [e["kind"] for e in doc["recorder"]["events"]]
    assert ring_kinds, "empty ring slice"
    return doc


@pytest.mark.chaos
def test_trigger_breaker_open_dumps_one_bundle(model, bundles):
    breaker = CircuitBreaker(name="bo", failure_threshold=1)
    with faults.injected({"serve.dispatch": {"mode": "raise", "nth": 1,
                                             "count": 1}}):
        with ServingRuntime(model, "bo", _cfg(), breaker=breaker) as rt:
            rec = rt.score(_rows(1)[0], timeout=30)
            assert rec is not None  # degraded eager, never failed
    doc = _assert_single_valid_bundle(bundles(), "breaker_open")
    assert doc["trigger"]["detail"]["model"] == "bo"
    ring = [e["kind"] for e in doc["recorder"]["events"]]
    assert "breaker" in ring  # the open transition itself
    assert "chaos.injection" in ring  # ... and what provoked it
    # the serve-local registry snapshot rode along (the dump happens at
    # the open transition, before the flush finishes counting its rows —
    # the breaker gauge already reads open=2.0)
    assert doc["metrics"]["tg_breaker_state"]["model=bo"] == 2.0


@pytest.mark.chaos
def test_trigger_oom_downshift_dumps_one_bundle(model, bundles):
    with faults.injected({"oom.serve": {"mode": "oom", "nth": 1,
                                        "count": 1}}):
        rt = ServingRuntime(model, "oom", _cfg(), auto_start=False)
        try:
            futs = [rt.submit(r) for r in _rows(4)]
            rt.start()
            recs = [f.result(timeout=30) for f in futs]
            assert all(r is not None for r in recs)
        finally:
            rt.close()
    assert rt.summary()["faults"]["oomDownshifts"] == 1
    doc = _assert_single_valid_bundle(bundles(), "oom_downshift")
    assert doc["trigger"]["detail"]["site"] == "oom.serve"
    assert doc["faults"]["oomDownshifts"], "FaultLog must ride along"


def test_trigger_drift_degraded_dumps_one_bundle(model, bundles):
    baseline = DriftBaseline.from_model(model)
    mon = DriftMonitor(baseline, DriftConfig(every_rows=64, min_rows=64),
                       model_name="dd")
    rng = np.random.RandomState(5)
    mon.observe([{"x1": float(rng.randn() + 9.0),
                  "x2": float(rng.randn())} for _ in range(256)])
    assert mon.verdict() == DEGRADED
    doc = _assert_single_valid_bundle(bundles(), "drift_degraded")
    assert doc["trigger"]["detail"]["model"] == "dd"
    assert doc["state"]["drift"]["verdict"] == DEGRADED
    ring = [e["kind"] for e in doc["recorder"]["events"]]
    assert "drift.verdict" in ring


def test_trigger_watchdog_stall_dumps_one_bundle(bundles):
    clock = {"t": 0.0}
    dog = wd.Watchdog(stall_after=5.0, clock=lambda: clock["t"],
                      start_thread=False)
    heart = dog.register("tg-test-thread", kind="test.loop")
    try:
        clock["t"] = 6.0
        fired = dog.check_now()
        assert [h.name for h in fired] == ["tg-test-thread"]
    finally:
        heart.close()
    doc = _assert_single_valid_bundle(bundles(), "thread_stalled")
    assert doc["trigger"]["detail"]["site"] == "watchdog.test.loop"
    assert doc["trigger"]["detail"]["thread"] == "tg-test-thread"


def test_trigger_unclean_exit_dumps_one_bundle(tmp_path, bundles):
    rng = np.random.RandomState(3)
    df = pd.DataFrame({"x1": rng.randn(200), "x2": rng.randn(200)})
    df["y"] = ((df.x1 + df.x2) > 0).astype(float)
    ckpt = str(tmp_path / "ckpt")

    def wf():
        label = FeatureBuilder.RealNN("y").extract_field().as_response()
        feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
                 for c in ("x1", "x2")]
        checked = tg.transmogrify(feats).sanity_check(label)
        pred = (BinaryClassificationModelSelector.with_cross_validation(
            seed=9, models=[("OpLogisticRegression",
                             [{"regParam": 0.01, "elasticNetParam": 0.0}])])
            .set_input(label, checked).get_output())
        return (OpWorkflow().set_input_dataset(df)
                .set_result_features(pred).with_checkpoint_dir(ckpt))

    wf().train()
    assert bundles() == []  # a clean train triggers nothing
    # forge the dying breath of another process killed mid-upload
    atomic_write_json(os.path.join(ckpt, SENTINEL_FILE),
                      {"pid": 999_999_999, "phase": "device_upload"})
    wf().train(resume=True)
    doc = _assert_single_valid_bundle(bundles(), "unclean_exit")
    detail = doc["trigger"]["detail"]
    assert detail["pid"] == 999_999_999
    assert detail["phase"] == "device_upload"
    assert detail["oomKillSuspected"] is True


# ---------------------------------------------------------------------------
# Rate limit + schema round-trip
# ---------------------------------------------------------------------------

def test_dump_rate_limit(bundles, monkeypatch):
    monkeypatch.setenv("TG_POSTMORTEM_MAX", "2")
    paths = [pm.trigger("breaker_open", detail={"n": i}) for i in range(4)]
    assert [p is not None for p in paths] == [True, True, False, False]
    assert len(bundles()) == 2
    assert pm.dump_counts() == {"dumped": 2, "suppressed": 2}
    # suppressed triggers still leave evidence in the ring
    kinds = [e.kind for e in bb.recorder().events()]
    assert kinds.count("postmortem.suppressed") == 2
    assert kinds.count("postmortem") == 2


def test_bundle_schema_round_trip(bundles):
    corr = bb.new_correlation_id("req")
    bb.record("serve.enqueue", corr=corr, model="m")
    bb.record("serve.resolve", corr=corr, model="m", seconds=0.01)
    from transmogrifai_tpu.robustness.policy import FaultLog, FaultReport
    log = FaultLog()
    log.add(FaultReport(site="s", kind="oom_downshift", detail={"a": 1}))
    reg = om.MetricsRegistry()
    reg.counter("tg_x_total").inc(3)
    path = pm.trigger("oom_downshift", corr=corr,
                      detail={"site": "s"}, fault_log=log, metrics=reg,
                      state={"extra": {"k": "v"}})
    doc = json.loads(open(path).read())
    assert pm.validate_bundle(doc) == []
    assert doc["trigger"]["corr"] == corr
    # the correlated timeline is exactly this correlation id's events
    assert [e["kind"] for e in doc["correlated"]] == [
        "serve.enqueue", "serve.resolve"]
    assert all(e["corr"] == corr for e in doc["correlated"])
    assert doc["metrics"]["tg_x_total"][""] == 3.0
    assert doc["faults"]["oomDownshifts"][0]["detail"] == {"a": 1}
    assert doc["state"]["extra"] == {"k": "v"}
    assert doc["environment"].get("jax"), "jax provenance must ride along"
    # corrupted docs are caught
    assert pm.validate_bundle({"schemaVersion": 99})
    bad = dict(doc)
    bad["trigger"] = {**doc["trigger"], "kind": "not_a_trigger"}
    assert any("unknown trigger kind" in p for p in pm.validate_bundle(bad))


# ---------------------------------------------------------------------------
# Doctor rendering
# ---------------------------------------------------------------------------

def test_cli_doctor_renders_bundle(model, bundles, capsys):
    with ServingRuntime(model, "dr", _cfg()) as rt:
        futs = [rt.submit(r) for r in _rows(3)]
        [f.result(timeout=30) for f in futs]
        corr = futs[0].tg_corr
        path = pm.trigger("breaker_open", corr=corr,
                          detail={"model": "dr"},
                          fault_log=rt.fault_log, metrics=rt.metrics)
    from transmogrifai_tpu.cli import main as cli_main
    cli_main(["doctor", path])
    out = capsys.readouterr().out
    assert "doctor verdict: ok" in out
    assert "breaker_open" in out
    assert corr in out  # the correlated timeline names the request
    assert "serve.resolve" in out
    # directory mode picks the newest bundle; --json is machine-readable
    cli_main(["doctor", os.path.dirname(path), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc["problems"] == [] and doc["doc"]["trigger"]["kind"] == \
        "breaker_open"


# ---------------------------------------------------------------------------
# Loadgen slowest-K + campaign attach
# ---------------------------------------------------------------------------

def test_loadgen_names_slowest_requests(model):
    rows = synthetic_rows(model, 64, seed=1)
    with ServingRuntime(model, "lg", _cfg(max_batch=16)) as rt:
        rep = run_open_loop(rt, rows, seconds=0.5, rps=200.0)
    assert rep["completed"] > 0 and rep["accountingOk"]
    slowest = rep["slowestRequests"]
    assert 0 < len(slowest) <= 5
    assert all(d["corr"].startswith("req-") and d["ms"] >= 0
               for d in slowest)
    # descending and genuinely the tail: the worst named request is as
    # slow as any named request
    ms = [d["ms"] for d in slowest]
    assert ms == sorted(ms, reverse=True)
    # each id resolves to a recorder timeline
    kinds = [e.kind for e in bb.recorder().slice_for(slowest[0]["corr"])]
    assert "serve.enqueue" in kinds and "serve.resolve" in kinds


@pytest.mark.campaign
def test_campaign_violation_attaches_bundle_to_repro(bundles, monkeypatch):
    from transmogrifai_tpu.robustness.campaign import ChaosCampaign
    eng = ChaosCampaign(seed=3, scenarios=["transfer"])
    try:
        scn = eng.scenarios["transfer"]
        monkeypatch.setattr(
            type(scn), "violations",
            lambda self, result, fired, log: ["forced violation"])
        report = eng.run(schedules=[
            {"scenario": "transfer",
             "faults": {"distributed.to_host":
                        {"mode": "raise", "nth": 1, "count": 1,
                         "transient": True}}}])
    finally:
        eng.close()
    assert not report.ok
    entry = report.violations[0]
    path = entry["postmortem"]
    assert os.path.isfile(path)
    assert entry["repro"]["postmortem"] == path
    doc = pm.read_bundle(path)
    assert pm.validate_bundle(doc) == []
    assert doc["trigger"]["kind"] == "campaign_violation"
    assert doc["trigger"]["detail"]["violations"] == ["forced violation"]
    assert doc["trigger"]["detail"]["cmd"].startswith("TG_CHAOS=1")


# ---------------------------------------------------------------------------
# Prometheus bucket exposition (satellite)
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? "
    r"[-+]?(?:[0-9.]+(?:e[-+]?[0-9]+)?|inf|nan|Inf|NaN))$")


def test_prometheus_histogram_buckets_valid_and_cumulative():
    reg = om.MetricsRegistry()
    h = reg.histogram("tg_lat_seconds", help="latency", model="m")
    rng = np.random.RandomState(0)
    vals = np.abs(rng.randn(500)) * 0.01
    for v in vals:
        h.observe(float(v))
    text = reg.to_prometheus()
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"invalid prometheus line: {line!r}"
    assert "# TYPE tg_lat_seconds histogram" in text
    buckets = re.findall(
        r'tg_lat_seconds_bucket\{model="m",le="([^"]+)"\} ([0-9.]+|500)',
        text)
    assert len(buckets) >= 3
    les = [b[0] for b in buckets]
    assert les[-1] == "+Inf"
    counts = [float(b[1]) for b in buckets]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert counts[-1] == 500  # +Inf is the exact count
    finite = [float(le) for le in les[:-1]]
    assert finite == sorted(finite), "boundaries must ascend"
    assert "tg_lat_seconds_sum" in text
    assert "tg_lat_seconds_count" in text
    # compat flag restores the old summary exposition untouched
    compat = reg.to_prometheus(compat=True)
    assert "_bucket" not in compat
    assert 'tg_lat_seconds{model="m",quantile="0.5"}' in compat
    assert "# TYPE tg_lat_seconds summary" in compat


# ---------------------------------------------------------------------------
# Overhead guard
# ---------------------------------------------------------------------------

def test_recorder_overhead_on_serve_burst(model):
    """The always-on recorder must be serve-burst cheap: score the same
    burst through the runtime with the recorder on and off; the on-path
    wall clock must stay within 1.5× of the off-path (generous for CI
    noise)."""
    rows = _rows(256, seed=9)
    mb = micro_batch_score_function(model)
    mb(rows[:8])  # compile warmup outside the measured region
    # the warmup's plan/segment builds land in the ring as `compile`
    # events (the ledger is recorder-visible by design, PR 12) — drop
    # them so the disabled-burst assertion below sees only burst writes
    bb.recorder().clear()

    def burst(name):
        with ServingRuntime(model, name,
                            _cfg(max_batch=64, max_queue=512)) as rt:
            rt.warm()
            t0 = time.perf_counter()
            futs = [rt.submit(r) for r in rows]
            [f.result(timeout=60) for f in futs]
            return time.perf_counter() - t0

    bb.enable_blackbox(False)
    try:
        off = burst("bb-off")
        assert not bb.recorder().events(), "disabled recorder must not write"
    finally:
        bb.enable_blackbox(None)
    on = burst("bb-on")
    assert bb.recorder().events(), "enabled recorder saw no serve events"
    assert on <= off * 1.5 + 0.05, (
        f"recorder-on burst {on:.3f}s vs off {off:.3f}s")
