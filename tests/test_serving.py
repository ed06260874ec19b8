"""Resilient serving runtime (transmogrifai_tpu/serving; docs/serving.md):
continuous batching bit-equality, backpressure + deadline shedding,
breaker open→half-open→close under ``serve.dispatch`` chaos with
degraded-vs-eager bit-equality, quarantine preservation through the
queue, registry health/warm-start, the FaultLog ring bound, and the
chaos soak (all three serve sites + 2× overload, zero crashes)."""
import json
import os
import time

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.local import micro_batch_score_function, score_function
from transmogrifai_tpu.local.scoring import SCORE_ERROR_KEY
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.robustness.policy import FaultLog, FaultReport
from transmogrifai_tpu.serving import (
    CircuitBreaker, DeadlineExceededError, ModelRegistry, OverloadError,
    RuntimeStoppedError, ServeConfig, ServingRuntime,
)
from transmogrifai_tpu.serving.loadgen import run_open_loop, synthetic_rows
from transmogrifai_tpu.workflow import OpWorkflow

pytestmark = pytest.mark.serve


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


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

def test_batched_results_bit_equal_singleton(model):
    """Requests coalesced into one flush must return byte-identical
    records to scoring each row alone through the micro-batch path (the
    plan padding buckets guarantee one compiled program serves both)."""
    rows = _rows(8)
    mb = micro_batch_score_function(model)
    singleton = [mb([r])[0] for r in rows]
    with ServingRuntime(model, "bit", _cfg()) as rt:
        futs = [rt.submit(r) for r in rows]
        batched = [f.result(timeout=30) for f in futs]
    assert batched == singleton
    # every flush was a real coalesce, not 8 singleton dispatches
    snap = rt.metrics.snapshot()
    assert snap["tg_serve_rows_total"]["model=bit"] == 8.0
    batches = snap["tg_serve_batch_rows"]["model=bit"]["count"]
    assert batches < 8


def test_flush_on_size_and_on_deadline(model):
    """A full max_batch flushes immediately; a partial batch flushes once
    the oldest request ages past max_wait_ms — it must not wait for the
    batch to fill."""
    with ServingRuntime(model, "flush", _cfg(max_batch=4,
                                             max_wait_ms=30.0)) as rt:
        t0 = time.monotonic()
        futs = [rt.submit(r) for r in _rows(4)]
        [f.result(timeout=30) for f in futs]
        full_latency = time.monotonic() - t0
        assert full_latency < 5.0
        # single request: resolves via the max_wait timer, not batch fill
        out = rt.score(_rows(1)[0], timeout=30)
        assert out is not None


# ---------------------------------------------------------------------------
# Backpressure + deadlines
# ---------------------------------------------------------------------------

def test_queue_full_sheds_with_typed_overload_error(model):
    rt = ServingRuntime(model, "of", _cfg(max_queue=2), auto_start=False)
    try:
        rt.submit({"x1": 0.1, "x2": 0.2})
        rt.submit({"x1": 0.1, "x2": 0.2})
        with pytest.raises(OverloadError, match="full"):
            rt.submit({"x1": 0.1, "x2": 0.2})
        snap = rt.metrics.snapshot()
        assert snap["tg_serve_shed_total"]["model=of,reason=overload"] == 1.0
        assert rt.summary()["shed"]["overload"] == 1.0
    finally:
        rt.start()   # drain the two accepted requests
        rt.close()


def test_deadline_expiry_sheds_before_dispatch(model, monkeypatch):
    """A request whose deadline passed while queued must fail with
    DeadlineExceededError and never reach the compiled scorer."""
    rt = ServingRuntime(model, "dl", _cfg(), auto_start=False)
    dispatched = []
    # count rows entering the gather stage (the pipelined compiled path);
    # also wrap the monolithic scorer so a serial (depth-1) run or a
    # fallback path is counted identically
    real_gather = rt._stages.gather
    monkeypatch.setattr(
        rt._stages, "gather", lambda rows: dispatched.append(len(rows))
        or real_gather(rows))
    real_scorer = rt._scorer
    monkeypatch.setattr(
        rt, "_scorer", lambda rows: dispatched.append(len(rows))
        or real_scorer(rows))
    expired = rt.submit({"x1": 0.3, "x2": 0.0}, deadline_ms=1)
    alive = rt.submit({"x1": 0.4, "x2": 0.1}, deadline_ms=60_000)
    time.sleep(0.05)  # let the first deadline lapse before the batcher runs
    rt.start()
    try:
        with pytest.raises(DeadlineExceededError, match="shed before"):
            expired.result(timeout=30)
        assert alive.result(timeout=30) is not None
        # the expired request was shed pre-dispatch: only 1 row dispatched
        assert dispatched == [1]
        assert rt.summary()["shed"]["deadline"] == 1.0
    finally:
        rt.close()


def test_stopped_runtime_refuses_requests(model):
    rt = ServingRuntime(model, "stop", _cfg())
    rt.close()
    with pytest.raises(RuntimeStoppedError):
        rt.submit({"x1": 0.0, "x2": 0.0})


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_breaker_open_halfopen_close_under_dispatch_chaos(model):
    """serve.dispatch chaos: N consecutive dispatch failures open the
    breaker; while open, requests degrade to the eager per-row path with
    BIT-EQUAL results (never fail); after reset_after the half-open probe
    re-tries the device path and closes on success. All recorded via
    FaultLog + the tg_breaker_state gauge."""
    clk = [0.0]
    br = CircuitBreaker(name="cb", failure_threshold=2, reset_after=10.0,
                        clock=lambda: clk[0])
    row = {"x1": 0.4, "x2": -0.2}
    eager = score_function(model)(row)
    with faults.injected({"serve.dispatch": {
            "mode": "raise", "nth": 1, "count": 2, "transient": True}}):
        with ServingRuntime(model, "cb", _cfg(max_wait_ms=1.0),
                            breaker=br) as rt:
            gauge = rt.metrics.snapshot()["tg_breaker_state"]["model=cb"]
            assert gauge == 0.0
            r1 = rt.score(row, timeout=30)   # dispatch fault 1: degraded
            assert br.state == "closed" and r1 == eager
            r2 = rt.score(row, timeout=30)   # dispatch fault 2: opens
            assert br.state == "open" and r2 == eager
            assert rt.metrics.snapshot()[
                "tg_breaker_state"]["model=cb"] == 2.0
            assert rt.health_state() == "degraded"
            r3 = rt.score(row, timeout=30)   # open: eager, no device call
            assert br.state == "open" and r3 == eager
            clk[0] = 20.0                    # past reset_after
            r4 = rt.score(row, timeout=30)   # half-open probe succeeds
            assert br.state == "closed" and r4 == eager
            assert rt.metrics.snapshot()[
                "tg_breaker_state"]["model=cb"] == 0.0
            s = rt.summary()
            assert s["degradedRows"] == 3.0
            assert s["breaker"]["opens"] == 1 and s["breaker"]["probes"] == 1
    # every degraded batch is on the serve-scoped FaultLog
    degraded = rt.fault_log.of_kind("breaker_degraded")
    assert len(degraded) == 3
    assert {r.site for r in degraded} == {"serve.dispatch"}
    assert rt.fault_log.to_json()["breakerDegraded"]


@pytest.mark.chaos
def test_failed_probe_reopens(model):
    clk = [0.0]
    br = CircuitBreaker(name="rp", failure_threshold=1, reset_after=5.0,
                        clock=lambda: clk[0])
    row = {"x1": 0.2, "x2": 0.1}
    with faults.injected({"serve.dispatch": {
            "mode": "raise", "nth": 1, "count": 2, "transient": True}}):
        with ServingRuntime(model, "rp", _cfg(max_wait_ms=1.0),
                            breaker=br) as rt:
            rt.score(row, timeout=30)        # fault 1: opens (threshold 1)
            assert br.state == "open"
            clk[0] = 10.0
            rt.score(row, timeout=30)        # probe hits fault 2: reopens
            assert br.state == "open"
            assert br.snapshot()["opens"] == 2
            clk[0] = 20.0
            rt.score(row, timeout=30)        # probe succeeds: closes
            assert br.state == "closed"


@pytest.mark.chaos
def test_flush_chaos_degrades_batch_without_failing(model):
    row = {"x1": 0.5, "x2": 0.3}
    eager = score_function(model)(row)
    with faults.injected({"serve.flush": {
            "mode": "raise", "nth": 1, "count": 1, "transient": True}}):
        with ServingRuntime(model, "fl", _cfg(max_wait_ms=1.0)) as rt:
            out = rt.score(row, timeout=30)
    assert out == eager
    # flush faults degrade but do NOT count toward the breaker
    assert rt.breaker.snapshot()["consecutiveFailures"] == 0
    (rep,) = rt.fault_log.of_kind("breaker_degraded")
    assert rep.site == "serve.flush"


@pytest.mark.chaos
def test_enqueue_chaos_is_typed_and_runtime_survives(model):
    with faults.injected({"serve.enqueue": {
            "mode": "raise", "nth": 1, "count": 1, "transient": True}}):
        with ServingRuntime(model, "eq", _cfg(max_wait_ms=1.0)) as rt:
            with pytest.raises(faults.TransientFaultError):
                rt.submit({"x1": 0.1, "x2": 0.1})
            # the runtime is untouched: the next request scores normally
            out = rt.score({"x1": 0.1, "x2": 0.1}, timeout=30)
    assert out is not None and SCORE_ERROR_KEY not in out


# ---------------------------------------------------------------------------
# Quarantine through the queue
# ---------------------------------------------------------------------------

def test_score_error_quarantine_preserved_through_queue(model):
    with ServingRuntime(model, "qr", _cfg(max_wait_ms=5.0)) as rt:
        f_good = rt.submit({"x1": 0.5, "x2": 0.1})
        f_bad = rt.submit({"x1": "not-a-number", "x2": 0.1})
        good, bad = f_good.result(timeout=30), f_bad.result(timeout=30)
    assert SCORE_ERROR_KEY not in good
    assert SCORE_ERROR_KEY in bad
    assert all(v is None for k, v in bad.items() if k != SCORE_ERROR_KEY)
    assert rt.summary()["quarantinedRows"] == 1.0


# ---------------------------------------------------------------------------
# Registry + warm start
# ---------------------------------------------------------------------------

def test_registry_health_snapshot_and_isolation(model):
    with ModelRegistry(_cfg(max_wait_ms=1.0)) as reg:
        reg.register("a", model)
        reg.register("b", model)
        assert reg.names() == ["a", "b"]
        with pytest.raises(ValueError, match="already registered"):
            reg.register("a", model)
        reg.score("a", {"x1": 0.1, "x2": 0.2}, timeout=30)
        h = reg.health()
        assert h["ready"] is True
        assert set(h["models"]) == {"a", "b"}
        ha = h["models"]["a"]
        assert ha["state"] == "ready"
        assert ha["breaker"]["state"] == "closed"
        assert ha["latency"]["count"] == 1
        assert {"p50", "p95", "p99"} <= set(ha["latency"])
        assert h["models"]["b"]["rowsScored"] == 0.0  # per-model isolation
        # one model's breaker opening degrades only itself
        reg.runtime("b").breaker.record_failure()
        reg.runtime("b").breaker.record_failure()
        reg.runtime("b").breaker.record_failure()
        h = reg.health()
        assert h["models"]["b"]["state"] == "degraded"
        assert h["models"]["a"]["state"] == "ready"
        assert h["ready"] is False
    assert reg.names() == []


def test_save_records_serving_fingerprint_and_load_pretraces(model, tmp_path):
    """Warm-start hook: save_model records the serve plan schema
    fingerprint in MANIFEST.json; registry.load pre-traces it so the first
    request is served without building a new plan."""
    from transmogrifai_tpu import plan as plan_mod

    path = str(tmp_path / "model")
    model.save(path)
    man = json.loads(open(os.path.join(path, "MANIFEST.json")).read())
    entry = man["serving"]
    assert entry["resultFeatures"]
    cols = [c[0] for c in entry["planFingerprint"]]
    assert "x1" in cols and "x2" in cols
    plan_mod.clear_plan_cache()
    with ModelRegistry(_cfg(max_wait_ms=1.0)) as reg:
        rt = reg.load("warm", path)
        assert rt.warm_info["ok"] is True
        assert rt.warm_info["fingerprintMatch"] is True
        assert rt.warm_info["plansWarmed"] >= 1
        warmed = plan_mod.cache_stats()["entries"]
        out = reg.score("warm", {"x1": 0.4, "x2": -0.2}, timeout=30)
        # zero retrace: the first real request hit the pre-traced plan
        assert plan_mod.cache_stats()["entries"] == warmed
        assert SCORE_ERROR_KEY not in out
        assert reg.health()["models"]["warm"]["warm"]["plansWarmed"] >= 1


def test_loaded_model_serves_bit_equal_to_original(model, tmp_path):
    path = str(tmp_path / "model")
    model.save(path)
    rows = _rows(4, seed=11)
    mb = micro_batch_score_function(model)
    expect = mb(rows)
    with ModelRegistry(_cfg(max_wait_ms=2.0)) as reg:
        rt = reg.load("m", path)
        futs = [rt.submit(r) for r in rows]
        got = [f.result(timeout=30) for f in futs]
    assert got == expect


# ---------------------------------------------------------------------------
# FaultLog ring (satellite)
# ---------------------------------------------------------------------------

def test_fault_log_ring_bounds_reports(monkeypatch):
    monkeypatch.setenv("TG_FAULTS_MAX", "8")
    log = FaultLog()
    for i in range(20):
        log.add(FaultReport(site="s", kind="retry", detail={"i": i}))
    assert len(log.reports) == 8
    assert log.dropped == 12
    # newest reports win: the ring keeps the tail, not the head
    assert [r.detail["i"] for r in log.reports] == list(range(12, 20))
    assert log.to_json()["droppedReports"] == 12
    # explicit constructor bound beats the env
    small = FaultLog(max_reports=2)
    for i in range(5):
        small.add(FaultReport(site="s", kind="retry"))
    assert len(small.reports) == 2 and small.dropped == 3


def test_fault_log_default_bound_and_ambient_record():
    log = FaultLog()
    assert log.max_reports == 1024
    with log.activate():
        FaultLog.record(FaultReport(site="amb", kind="retry"))
    assert len(log.reports) == 1
    FaultLog.record(FaultReport(site="amb", kind="retry"))  # no-op, no raise


# ---------------------------------------------------------------------------
# Chaos soak: all three serve sites + 2× overload, zero crashes
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_soak_all_sites_with_overload(model):
    """Acceptance shape (``op serve`` drives the same generator longer):
    faults at serve.enqueue / serve.flush / serve.dispatch plus an
    open-loop load far above capacity over a tiny queue. The run must
    complete with every request resolved (result or typed shed), the
    breaker visible in summary(), and the runtime still alive."""
    rows = _rows(64, seed=5)
    with faults.injected({
            "serve.enqueue": {"mode": "raise", "nth": 10, "count": 3,
                              "transient": True},
            "serve.flush": {"mode": "raise", "nth": 2, "count": 1,
                            "transient": True},
            "serve.dispatch": {"mode": "raise", "nth": 2, "count": 4,
                               "transient": True}}):
        with ServingRuntime(model, "soak",
                            _cfg(max_batch=16, max_queue=32,
                                 max_wait_ms=1.0,
                                 breaker_failures=3,
                                 breaker_reset_ms=50.0)) as rt:
            report = run_open_loop(rt, rows, seconds=1.0, rps=2000.0,
                                   deadline_ms=150.0)
            summary = rt.summary()
            assert rt.running
    # no crashes: every offered request is accounted for
    accounted = (report["completed"] + report["shedOverload"]
                 + report["shedDeadline"] + report["submitErrors"]
                 + report["failed"])
    assert accounted == report["offered"]
    assert report["failed"] == 0            # no untyped failures
    assert report["completed"] > 0          # progress under chaos
    assert report["shedOverload"] > 0       # 2×+ overload did shed
    assert report["submitErrors"] == 3      # the 3 enqueue faults
    assert summary["degradedRows"] >= 1     # flush/dispatch faults degraded
    # shed/breaker/quarantine counts all visible in summary()
    assert {"shed", "breaker", "degradedRows",
            "quarantinedRows"} <= set(summary)
    assert summary["breaker"]["opens"] >= 1  # 4 consecutive dispatch faults


def test_loadgen_synthetic_rows_match_schema(model):
    rows = synthetic_rows(model, 16, seed=2)
    assert len(rows) == 16
    assert {"x1", "x2", "y"} <= set(rows[0])
    out = micro_batch_score_function(model)(rows[:4])
    assert all(SCORE_ERROR_KEY not in r for r in out)


# ---------------------------------------------------------------------------
# Observability integration
# ---------------------------------------------------------------------------

def test_serve_metrics_mirrored_when_enabled(model):
    from transmogrifai_tpu.observability import metrics as om
    from transmogrifai_tpu.observability import summarize

    om.enable_metrics(True)
    try:
        with ServingRuntime(model, "obs", _cfg(max_wait_ms=1.0)) as rt:
            rt.score({"x1": 0.1, "x2": 0.0}, timeout=30)
        obs = summarize()
        assert obs["serving"]["tg_serve_rows_total"]["model=obs"] == 1.0
        assert "tg_breaker_state" in obs["serving"]
        # serve series live in the serving section, not counters
        assert not any(k.startswith("tg_serve_") for k in obs["counters"])
        prom = om.registry().to_prometheus()
        # round-11 exposition: real cumulative buckets (+Inf is exact);
        # the old quantile-summary lines live behind TG_PROM_SUMMARY_COMPAT
        assert 'tg_serve_request_seconds_bucket{model="obs",le="+Inf"} 1' \
            in prom
        assert 'tg_breaker_state{model="obs"}' in prom
        compat = om.registry().to_prometheus(compat=True)
        assert 'tg_serve_request_seconds{model="obs",quantile="0.99"}' \
            in compat
    finally:
        om.enable_metrics(None)


def test_serve_local_metrics_do_not_touch_global_registry(model):
    """Observability off (the default): serving keeps its own SLO registry
    but must write NOTHING to the process-global one."""
    from transmogrifai_tpu.observability import metrics as om

    assert not om.metrics_enabled()
    with ServingRuntime(model, "off", _cfg(max_wait_ms=1.0)) as rt:
        rt.score({"x1": 0.2, "x2": 0.1}, timeout=30)
    assert om.registry().snapshot() == {}
    assert rt.summary()["latency"]["count"] == 1


def test_vectorized_table_builder_byte_identical(model):
    """The serve hot path: the vectorized request→FeatureTable assembly must build a
    byte-identical table to the per-cell ``Column.of_values`` path for
    homogeneous batches, heterogeneous batches (None/strings) must fall
    back with the same result, and the row-major record view must emit
    the same python values."""
    from transmogrifai_tpu.local.scoring import (
        serve_record_builder, serve_table_builder)
    from transmogrifai_tpu.table import Column

    build = serve_table_builder(model)
    rows = _rows(64)
    rows[5] = {"x1": None, "x2": float("nan")}   # missing cells
    rows[6] = {"x2": 0.25}                       # missing field
    rows[7] = {"x1": True, "x2": 3}              # bool/int scalars
    table = build(rows)
    for f in model.raw_features:
        if f.is_response:
            continue
        vals = [f.origin_stage.extract(r) for r in rows]
        ref = Column.of_values(f.feature_type, vals)
        got = table[f.name]
        np.testing.assert_array_equal(np.asarray(ref.values),
                                      np.asarray(got.values))
        np.testing.assert_array_equal(ref.valid_mask(), got.valid_mask())
        assert np.asarray(got.values).dtype == np.asarray(ref.values).dtype
    # record view: same python values as the per-cell path
    scored = model.score(table=build(_rows(8)))
    recs = serve_record_builder(model)(scored, 8)
    for i, rec in enumerate(recs):
        for f in model.result_features:
            col = scored[f.name]
            v = np.asarray(col.values)[i]
            if f.type_name == "Prediction":
                keys = col.metadata.get("keys", ())
                assert rec[f.name] == {k: float(x) for k, x in zip(keys, v)}
            else:
                assert rec[f.name] == (v.tolist() if v.ndim else v.item())
