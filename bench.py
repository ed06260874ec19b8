"""North-star benchmark: ModelSelector model×fold fits/sec, 4-family.

The reference's hot loop is |models| × |paramMaps| × |folds| sequential Spark
fits throttled by an 8-thread pool (reference: OpValidator.scala:270-322,
OpCrossValidation.scala). BASELINE.md sets the target: >= 100 model×fold fits
per second on a 1M-row tabular dataset.

This drives the PRODUCT sweep path — ``OpCrossValidation.validate`` — over
the binary default selector's four families (LR + RandomForest + GBT +
LinearSVC, reference BinaryClassificationModelSelector Defaults :59-61), so
the heavy tree fits are in the measured loop: tree-batched histogram growth
(models/trees.py), fused forest-descent scoring (ops/forest.py), batched
masked metrics. The metric is (configurations × folds) / wall-clock of the
full validate() call, including host-side split construction.

Modes (BENCH_MODE env):
- ``both`` (default): runs ``default`` then ``dense`` and prints one JSON
  line per mode (dense LAST — the headline line). Driver-verifies the
  out-of-the-box number alongside the dense throughput number (round-3
  VERDICT asked for both).
- ``transform``: eager-vs-planned A/B of the transform DAG (vectorize →
  combine → sanity-slice → predict over BENCH_ROWS × BENCH_FEATURES) with
  the compile/execute/transfer phase breakdown — the fused transform-plan
  line (docs/plan.md, docs/benchmarks.md "Transform plan A/B").
- ``dense``: a RandomParamBuilder-scale sweep — 108 configs across the 4
  families × 3 folds = 324 fits. This is the throughput number: AutoML
  sweeps at this density are what the 8-thread reference pool grinds
  through in minutes.
- ``serve``: the resilient serving runtime under open-loop synthetic load
  (docs/serving.md). Six lines: recorder-off / ledger-off / sampler-off
  reference arms, a clean line at 0.35× of measured runtime capacity
  (sustained rows/sec + p50/p99 tail; the flight-recorder, compile-ledger
  and sampler+SLO overheads each asserted ≤2% of their off arms, and
  ZERO page-severity SLO alerts — burn-rate false positives fail the
  bench), the same load with the drift monitor folding every batch
  (overhead asserted ≤5% of the clean line), then a chaos soak at 2×
  capacity with faults armed at all three ``serve.*`` sites — the soak
  must complete with overflow shed as typed errors, the breaker/shed/
  degraded counts visible (zero process crashes), ≥1 page-severity SLO
  burn-rate alert fired, and a ``slo_budget_exhausted`` post-mortem
  bundle on disk (docs/observability.md "SLOs, budgets & burn rates").
- ``stream``: the out-of-core line — a 10M×64 synthetic chunk stream
  trained end-to-end via ``OpWorkflow.train(stream=...)`` (vectorize →
  sanity-check → streaming GBT), reporting rows/sec, peak device-resident
  bytes (asserted O(chunk)), and the feed's transfer/compute overlap
  (docs/streaming.md; BENCH_STREAM_ROWS / BENCH_STREAM_FEATURES /
  TG_STREAM_CHUNK_ROWS override the shape).
- ``pressure``: resource-exhaustion resilience (docs/robustness.md
  "Resource exhaustion & watchdog"). Forces ``oom.*`` chaos at every
  choke point — planned transform bisect (bit-equal asserted), sweep
  grid split (identical winner asserted), serve flush split (zero failed
  requests + bounded throughput loss asserted), stream chunk-budget
  halving (completion + downshift asserted) — and measures the unforced
  monitor+watchdog overhead against TG_WATCHDOG_S=0 on the clean serve
  and stream lines (asserted ≤2%).
- ``campaign``: the chaos-campaign soak (docs/robustness.md "Chaos
  campaigns") — BENCH_CAMPAIGN_SCHEDULES (200) seeded randomized
  multi-fault schedules over every registered chaos site and all six
  scenario harnesses; asserts 100% site coverage, zero invariant
  violations, and full serve request accounting, printing the minimized
  one-command reproducer when anything fires.
- ``sweep``: the tree-family throughput line (docs/trees.md) — a linear
  (LR) sweep and a tree (RF + GBT) sweep over the same table, one JSON
  line each (tree LAST), with a pinned tripwire on tree fits/sec as a
  ratio of the same-run linear line: a drop below the floor means the
  tree path (histogram engine, forest descent, fused sweep programs)
  regressed relative to linear, independent of host speed.
- ``default``: the exact stock default grids (45 configs incl. the
  depth-12 trees, 135 fits) — the path every
  ``BinaryClassificationModelSelector()`` user gets; fixed costs dominate.
- ``linear``: round-1's logistic-only sweep (compatibility).

Each line: {"metric", "value", "unit", "vs_baseline"}. vs_baseline is
value / 100 (the BASELINE.json north-star target; the reference publishes
no wall-clock numbers of its own).
"""
import json
import os
import time

import numpy as np


def _models(mode, registry):
    if mode not in ("dense", "default", "linear"):
        raise SystemExit(f"unknown BENCH_MODE {mode!r}: "
                         "use both | dense | default | linear | "
                         "transform | serve | stream | pressure | "
                         "campaign | sweep")
    if mode == "linear":
        grid = [{"regParam": r, "elasticNetParam": e}
                for r in (0.001, 0.003, 0.01, 0.03, 0.1, 0.2, 0.3, 0.5)
                for e in (0.0, 0.25, 0.5, 0.75, 1.0)]
        return [(registry["OpLogisticRegression"], grid)]
    fams = ("OpLogisticRegression", "OpRandomForestClassifier",
            "OpGBTClassifier", "OpLinearSVC")
    if mode == "default":
        return [(registry[f], registry[f].default_grid("binary"))
                for f in fams]
    # dense: RandomParamBuilder-scale grids over the same default families
    lr = [{"regParam": r, "elasticNetParam": e}
          for r in (0.001, 0.003, 0.01, 0.03, 0.1, 0.2, 0.3, 0.5)
          for e in (0.0, 0.25, 0.5, 0.75, 1.0)]                      # 40
    svc = [{"regParam": float(r)} for r in np.logspace(-4, 0, 20)]   # 20
    rf = [{"maxDepth": dd, "minInstancesPerNode": mi, "minInfoGain": mg,
           "numTrees": 50, "subsamplingRate": 1.0}
          for dd in (3, 6) for mi in (5, 10, 50, 100)
          for mg in (0.001, 0.01, 0.1)]                              # 24
    gbt = [{"maxDepth": dd, "minInstancesPerNode": mi, "minInfoGain": mg,
            "maxIter": 20, "stepSize": ss}
           for dd in (3, 6) for mi in (10, 100)
           for mg in (0.001, 0.01, 0.1) for ss in (0.1, 0.3)]        # 24
    return [(registry["OpLogisticRegression"], lr),
            (registry["OpRandomForestClassifier"], rf),
            (registry["OpGBTClassifier"], gbt),
            (registry["OpLinearSVC"], svc)]


def _ledger_mark():
    from transmogrifai_tpu.observability import ledger as obs_ledger
    return obs_ledger.ledger().mark()


def _ledger_phases(mark=0):
    """The uniform compile & memory block every BENCH_MODE line carries
    (docs/observability.md "Compile & memory ledger"): program builds
    since ``mark`` by classified cause, plus the peak shape-predicted and
    measured device bytes — so every bench number names what it compiled
    and what it would have allocated."""
    from transmogrifai_tpu.observability import devicemem as obs_devicemem
    from transmogrifai_tpu.observability import ledger as obs_ledger
    led = obs_ledger.ledger()
    causes = {}
    for r in led.since(mark):
        causes[r.cause] = causes.get(r.cause, 0) + 1
    peaks = obs_devicemem.observatory().peaks()
    return {
        "compiles": causes,
        "compilesTotal": max(0, led.total - mark),
        "peakPredictedBytes": peaks["predicted"],
        "peakMeasuredBytes": peaks["measured"],
    }


def _sweep_transfer_sum():
    """Total seconds the sweeps spent fetching metrics device→host so far
    (validators observe tg_sweep_transfer_seconds per resolve)."""
    from transmogrifai_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.registry().snapshot().get(
        "tg_sweep_transfer_seconds", {})
    return sum(v["sum"] for v in snap.values()) if snap else 0.0


def _run_mode(mode, Xd, yd, n, d, platform, folds, reps):
    import jax  # noqa: F401
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    from transmogrifai_tpu.observability import metrics as obs_metrics
    from transmogrifai_tpu.utils.jax_cache import cache_stats

    models = _models(mode, MODEL_REGISTRY)
    B = folds * sum(len(g) for _, g in models)

    def sweep():
        cv = OpCrossValidation(num_folds=folds, seed=0)
        best = cv.validate(models, Xd, yd, "binary", "AuROC", True, 2)
        # host materialization below ends the timed region on finished
        # device work, not on the enqueue
        for r in best.results:
            m = np.asarray(r.fold_metrics)
            assert np.all(np.isfinite(m))
        return best

    # phase attribution (docs/benchmarks.md "Phase breakdown"): the metrics
    # registry's transfer histogram splits the warm wall into execute vs
    # device->host fetch, and cold-minus-warm bounds the compile cost the
    # warmup paid; persistent-cache hit/miss counts tag whether that
    # compile was served from disk (TPU/GPU only — zero on CPU)
    obs_metrics.enable_metrics(True)
    lmark = _ledger_mark()
    try:
        cs0 = cache_stats()
        t0 = time.perf_counter()
        sweep()                              # compile warmup
        cold = time.perf_counter() - t0
        cs1 = cache_stats()
        tr0 = _sweep_transfer_sum()
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            sweep()
            times.append(time.perf_counter() - t0)
        transfer = (_sweep_transfer_sum() - tr0) / reps
    finally:
        obs_metrics.enable_metrics(None)
    # MEDIAN, not best-of: the recorded number must clear the target on a
    # typical run, not only on the quietest one
    dt = float(np.median(times))

    fits_per_sec = B / dt
    suffix = "" if mode == "dense" else f"_{mode}"
    print(json.dumps({
        "metric": (f"model_fold_fits_per_sec_4family{suffix}_"
                   f"{n}rows_{d}feat_{platform}"),
        "value": round(fits_per_sec, 2),
        "unit": "fits/sec",
        "vs_baseline": round(fits_per_sec / 100.0, 3),
        "phases": {
            "compileSecs": round(max(0.0, cold - dt), 3),
            "executeSecs": round(max(0.0, dt - transfer), 3),
            "transferSecs": round(transfer, 4),
            "cacheHits": cs1["hits"] - cs0["hits"],
            "cacheMisses": cs1["misses"] - cs0["misses"],
            **_ledger_phases(lmark),
        },
    }), flush=True)


#: BENCH_MODE=sweep tripwire: tree-family fits/sec as a fraction of the
#: same-run linear (LR) line. Histogram-grown trees are intrinsically
#: heavier than closed-form linear fits — measured 0.047 on the 1-core
#: CPU host at the bench shape (engine-routed, round 18); the floor is
#: measurement ÷ ~4 host-noise margin. A drop below it means the
#: tree path regressed RELATIVE to linear (histogram engine, forest
#: descent, or sweep fusion) — the ratio cancels machine speed.
_SWEEP_TREE_RATIO_FLOOR = 0.01


def _run_sweep_line(platform, folds, reps):
    """BENCH_MODE=sweep: the TREE-family throughput line (docs/trees.md,
    docs/benchmarks.md round 18). Times a linear (LR) sweep and a tree
    (RF + GBT) sweep of the same fold count over the same table through
    ``OpCrossValidation.validate``, prints one JSON line per family class
    (tree LAST — the headline), and trips if tree fits/sec falls below
    ``_SWEEP_TREE_RATIO_FLOOR`` of the same-run linear line. Both sweeps
    ride the fused per-family programs; the tree line is dominated by the
    histogram engine's ``build_node_hist`` contraction."""
    import jax.numpy as jnp
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY

    n = int(os.environ.get("BENCH_ROWS", 1_000_000))
    d = int(os.environ.get("BENCH_FEATURES", 64))
    rng = np.random.RandomState(0)
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d).astype(np.float32)
         + rng.randn(n) > 0).astype(np.float32)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)

    lr = [{"regParam": r, "elasticNetParam": e}
          for r in (0.001, 0.01, 0.1, 0.3) for e in (0.0, 0.5)]       # 8
    rf = [{"maxDepth": dd, "minInstancesPerNode": mi, "minInfoGain": 0.001,
           "numTrees": 20, "subsamplingRate": 1.0}
          for dd in (3, 5) for mi in (5, 100)]                        # 4
    gbt = [{"maxDepth": dd, "minInstancesPerNode": 10,
            "minInfoGain": 0.001, "maxIter": 10, "stepSize": ss}
           for dd in (3, 5) for ss in (0.1, 0.3)]                     # 4
    lines = [("linear", [(MODEL_REGISTRY["OpLogisticRegression"], lr)]),
             ("tree", [(MODEL_REGISTRY["OpRandomForestClassifier"], rf),
                       (MODEL_REGISTRY["OpGBTClassifier"], gbt)])]

    fps = {}
    for name, models in lines:
        B = folds * sum(len(g) for _, g in models)

        def sweep():
            best = OpCrossValidation(num_folds=folds, seed=0).validate(
                models, Xd, yd, "binary", "AuROC", True, 2)
            for r in best.results:
                m = np.asarray(r.fold_metrics)
                assert np.all(np.isfinite(m))

        lmark = _ledger_mark()
        t0 = time.perf_counter()
        sweep()                              # compile warmup
        cold = time.perf_counter() - t0
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            sweep()
            times.append(time.perf_counter() - t0)
        dt = float(np.median(times))
        fps[name] = B / dt
        doc = {
            "metric": (f"model_fold_fits_per_sec_{name}_sweep_"
                       f"{n}rows_{d}feat_{platform}"),
            "value": round(fps[name], 2),
            "unit": "fits/sec",
            "vs_baseline": round(fps[name] / 100.0, 3),
            "phases": {
                "compileSecs": round(max(0.0, cold - dt), 3),
                "executeSecs": round(dt, 3),
                **_ledger_phases(lmark),
            },
        }
        if name == "tree":
            ratio = fps["tree"] / max(fps["linear"], 1e-9)
            # vs the SAME-RUN linear line — the tripwire ratio cancels
            # host speed, so it travels across machines
            doc["vs_linear"] = round(ratio, 4)
            assert ratio >= _SWEEP_TREE_RATIO_FLOOR, (
                f"tree sweep fits/sec fell to x{ratio:.4f} of the "
                f"same-run linear line (floor "
                f"x{_SWEEP_TREE_RATIO_FLOOR}) — the tree path regressed "
                f"relative to linear: check the histogram engine "
                f"(histeng/), forest descent, or the fused sweep "
                f"programs (docs/trees.md)")
        print(json.dumps(doc), flush=True)


def _plan_transfer_sum():
    from transmogrifai_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.registry().snapshot().get(
        "tg_plan_transfer_seconds", {})
    return sum(v["sum"] for v in snap.values()) if snap else 0.0


def _run_transform_ab(n, d, platform, reps):
    """Eager-vs-planned transform DAG A/B (ISSUE 4 satellite): one fitted
    vectorize→combine→sanity→predict tail over an n×d table, dispatched
    stage-by-stage vs as a compiled transform plan. Prints one JSON line
    per arm (planned LAST) with the compile/execute/transfer breakdown;
    the ratio is the layer-fusion win the plan cache makes durable."""
    import numpy as np
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import plan as plan_mod
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.preparators import SanityChecker
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.observability import metrics as obs_metrics
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import Real, RealNN
    from transmogrifai_tpu.workflow import OpWorkflow

    rng = np.random.RandomState(0)
    cols = {f"x{i}": Column(Real, rng.randn(n).astype(np.float32),
                            rng.rand(n) < 0.95)
            for i in range(d)}
    w = rng.randn(d).astype(np.float32)
    logits = sum(np.where(np.asarray(cols[f"x{i}"].mask),
                          np.asarray(cols[f"x{i}"].values), 0.0) * w[i]
                 for i in range(d))
    cols["y"] = Column(RealNN, (logits > 0).astype(np.float32), None)
    # fit on a small prefix (the fit is not what this line measures),
    # transform the full table
    fit_rows = min(n, 50_000)
    table = FeatureTable(cols, n)
    fit_table = table.take(np.arange(fit_rows))

    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(f"x{i}").extract_field().as_predictor()
             for i in range(d)]
    checked = label.transform_with(SanityChecker(seed=1),
                                   tg.transmogrify(feats))
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=1, models=[("OpLogisticRegression",
                         [{"regParam": 0.01, "elasticNetParam": 0.0}])])
        .set_input(label, checked).get_output())
    model = (OpWorkflow().set_input_table(fit_table)
             .set_result_features(pred, checked).train())
    score_table = table.drop(["y"])

    obs_metrics.enable_metrics(True)
    try:
        results = {}
        for arm in ("eager", "planned"):
            plan_mod.clear_plan_cache()
            plan_mod.enable_planning(arm == "planned")
            lmark = _ledger_mark()
            try:
                t0 = time.perf_counter()
                model.score(table=score_table)   # compile warmup
                cold = time.perf_counter() - t0
                tr0 = _plan_transfer_sum()
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    out = model.score(table=score_table)
                    np.asarray(out[pred.name].values)  # force materialize
                    times.append(time.perf_counter() - t0)
                transfer = (_plan_transfer_sum() - tr0) / reps
            finally:
                plan_mod.enable_planning(None)
            dt = float(np.median(times))
            results[arm] = dt
            rows_per_sec = n / dt
            print(json.dumps({
                "metric": f"transform_rows_per_sec_{arm}_{n}rows_{d}feat_"
                          f"{platform}",
                "value": round(rows_per_sec, 1),
                "unit": "rows/sec",
                "vs_baseline": (round(results["eager"] / dt, 3)
                                if "eager" in results else 1.0),
                "phases": {
                    "compileSecs": round(max(0.0, cold - dt), 3),
                    "executeSecs": round(max(0.0, dt - transfer), 4),
                    "transferSecs": round(transfer, 4),
                    **_ledger_phases(lmark),
                },
            }), flush=True)
    finally:
        obs_metrics.enable_metrics(None)
        plan_mod.clear_plan_cache()


def _serve_model(n, d, seed=0):
    """A small fitted model for the serve lines: the serve bench measures
    the runtime (queueing, batching, dispatch), not the sweep."""
    import numpy as np
    import pandas as pd
    import transmogrifai_tpu as tg
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.workflow import OpWorkflow

    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    df = pd.DataFrame({f"x{i}": X[:, i] for i in range(d)})
    df["y"] = y
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(f"x{i}").extract_field().as_predictor()
             for i in range(d)]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=seed, models=[("OpLogisticRegression",
                            [{"regParam": 0.01, "elasticNetParam": 0.0}])])
        .set_input(label, checked).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred).train())


def _slo_page_fires(summary):
    """Cumulative page-severity SLO alert activations across a runtime
    summary's per-spec tracker snapshots (fired-then-cleared counts)."""
    total = 0
    for snap in (summary.get("slo") or {}).values():
        total += int((snap.get("fired") or {}).get("page", 0))
    return total


def _run_serve(platform):
    """BENCH_MODE=serve: sustained rows/sec + tail latency + shed rate
    from the open-loop generator, clean and under chaos at 2× capacity
    (docs/benchmarks.md "Serving"; acceptance: the faulted line completes
    with typed sheds and visible breaker/degraded counts — no crashes).
    Round 19 adds the same-run serial-vs-pipelined dataplane A/B with
    per-stage attribution and bit-equality probe (docs/serving.md
    "Pipelined dataplane")."""
    from transmogrifai_tpu.local import micro_batch_score_function
    from transmogrifai_tpu.robustness import faults
    from transmogrifai_tpu.serving import ServeConfig, ServingRuntime
    from transmogrifai_tpu.serving.loadgen import (
        run_open_loop, synthetic_rows)

    n = int(os.environ.get("BENCH_SERVE_FIT_ROWS", 4000))
    d = int(os.environ.get("BENCH_SERVE_FEATURES", 16))
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", 3.0))
    model = _serve_model(n, d)
    max_batch = int(os.environ.get("TG_SERVE_MAX_BATCH", 256))
    rows = synthetic_rows(model, 1024, seed=1)

    # capacity probes. The raw micro-batch number bounds what the device
    # path can do; the runtime number (loadgen + batcher sharing this
    # process) is what open-loop rates must calibrate against — offering
    # 0.7× the RAW capacity would turn the "clean" line into a second
    # overload line on CPU, where the generator and the scorer contend
    # for the same GIL.
    mb = micro_batch_score_function(model)
    batch = rows[:max_batch]
    mb(batch)  # compile warmup
    t0 = time.perf_counter()
    for _ in range(3):
        mb(batch)
    capacity = 3 * len(batch) / (time.perf_counter() - t0)
    cfg = ServeConfig.from_env()
    cfg.max_batch = max_batch
    cfg.max_queue = int(os.environ.get("TG_SERVE_QUEUE_MAX", 512))
    with ServingRuntime(model, "calibrate", cfg) as rt:
        rt.warm()
        cal = run_open_loop(rt, rows, min(1.5, seconds), capacity)
    runtime_capacity = max(cal["rowsPerSec"], 1.0)

    # warm-serve tripwire (PR 6's zero-retrace claim, ledger-enforced):
    # save → registry.load pre-trace → a real request must record ZERO
    # compiles; a violation prints each build with its classified cause
    # before failing the bench (docs/observability.md)
    import shutil as _shutil
    import tempfile as _tempfile

    from transmogrifai_tpu import plan as _plan_mod
    from transmogrifai_tpu.observability import ledger as _obs_ledger
    from transmogrifai_tpu.serving import ModelRegistry
    from transmogrifai_tpu.programstore import store as _pstore
    wdir = _tempfile.mkdtemp(prefix="tg_bench_warm_model_")
    try:
        model.save(wdir)  # populates <wdir>/programs at save (TG_AOT)
        _plan_mod.clear_plan_cache()
        with ModelRegistry(cfg) as reg:
            reg.load("warmgate", wdir)
            wmark = _obs_ledger.ledger().mark()
            reg.score("warmgate", rows[0], timeout=30)
            retraced = _obs_ledger.ledger().since(wmark)
            for r in retraced:
                print(json.dumps({"warmServeViolation": r.to_json()}),
                      flush=True)
            assert not retraced, (
                f"warm serve path retraced {len(retraced)} program(s) "
                f"after registry.load pre-trace — causes: "
                f"{[r.cause for r in retraced]}")

        # ---- cold-start lines (round 15; docs/serving.md "AOT cold
        # start & the program store"): registry.load() -> first-request
        # latency, measured three ways against the SAME saved model —
        # cold (no pre-trace: the first request pays plan build + trace
        # + compile), warm (the PR 6 pre-trace: load pays it), AOT (the
        # program store: nothing traces anywhere — the zero-compile
        # gate marks BEFORE the load and must see an empty ledger after
        # the first real request).
        def _cold_start(arm):
            _plan_mod.clear_plan_cache()
            _pstore.close_sessions()
            if arm != "aot":
                _pstore.enable_aot(False)
            try:
                mark = _obs_ledger.ledger().mark()
                t0 = time.perf_counter()
                with ModelRegistry(cfg) as reg2:
                    rt = reg2.load("coldstart", wdir,
                                   warm=(arm != "cold"))
                    t_load = time.perf_counter() - t0
                    t1 = time.perf_counter()
                    reg2.score("coldstart", rows[0], timeout=30)
                    t_first = time.perf_counter() - t1
                    builds = _obs_ledger.ledger().since(mark)
                    warm_info = dict(rt.warm_info or {})
            finally:
                _pstore.enable_aot(None)
                _pstore.close_sessions()
            return {"loadS": round(t_load, 4),
                    "firstRequestS": round(t_first, 4),
                    "totalS": round(t_load + t_first, 4),
                    "compiles": len(builds),
                    "aotHits": warm_info.get("aotHits", 0)}, builds

        cold, _ = _cold_start("cold")
        warm_line, _ = _cold_start("warm")
        aot_line, aot_builds = _cold_start("aot")
        for r in aot_builds:
            print(json.dumps({"aotColdStartViolation": r.to_json()}),
                  flush=True)
        assert not aot_builds, (
            f"AOT cold start recorded {len(aot_builds)} ledger "
            f"build(s) across load + first request — causes: "
            f"{[r.cause for r in aot_builds]}")
        assert aot_line["aotHits"] > 0, (
            "AOT cold start deserialized nothing — the save-time "
            "populate did not ship programs")
        print(json.dumps({
            "metric": f"serve_cold_start_aot_speedup_{d}feat_{platform}",
            "value": round(cold["totalS"] / max(aot_line["totalS"],
                                                1e-9), 3),
            "unit": "x",
            "vs_baseline": round(cold["totalS"]
                                 / max(aot_line["totalS"], 1e-9), 3),
            "phases": {"cold": cold, "warm": warm_line, "aot": aot_line,
                       "warmVsAot": round(
                           warm_line["totalS"]
                           / max(aot_line["totalS"], 1e-9), 3)},
        }), flush=True)
    finally:
        _shutil.rmtree(wdir, ignore_errors=True)

    deadline_ms = float(os.environ.get("BENCH_SERVE_DEADLINE_MS", 250.0))
    # clean fraction 0.35: the saturated calibration number rides full-256
    # batches; at partial fill every flush still pays the full padded
    # dispatch, so 0.35× keeps the clean line inside the SLO region (zero
    # sheds) instead of producing a second overload line
    clean_frac = float(os.environ.get("BENCH_SERVE_CLEAN_FRACTION", 0.35))

    # ---- pipelined dataplane A/B (round 19; docs/serving.md "Pipelined
    # dataplane"): the SAME saturated open-loop load against depth 1
    # (the serial loop) and the overlapped pipeline, same run, same
    # model, same rows. A fixed probe slice must come back bit-equal
    # from both arms; per-stage wall time (tg_serve_stage_seconds) is
    # the phase attribution. The speedup / p99 tripwires only pay when
    # the device path and the Python stages can actually run
    # concurrently, so — like the fleet scaling gate below — they are
    # capability-gated on cores, with env-overridable floors.
    import dataclasses as _dataclasses
    pipe_depth = max(2, cfg.pipeline_depth)
    sat_rps = runtime_capacity * float(
        os.environ.get("BENCH_PIPE_SATURATION", 2.0))
    ab = {}
    for arm_name, depth in (("serial", 1), ("pipelined", pipe_depth)):
        acfg = _dataclasses.replace(cfg, pipeline_depth=depth)
        with ServingRuntime(model, f"ab{arm_name}", acfg) as rt:
            rt.warm()
            probe = [rt.submit(r) for r in rows[:64]]
            probe_recs = [f.result(timeout=60) for f in probe]
            rep = run_open_loop(rt, rows, seconds, sat_rps,
                                deadline_ms=deadline_ms)
            stage_snap = rt.metrics.snapshot().get(
                "tg_serve_stage_seconds", {})
            summary = rt.summary()
        stages = {}
        for key, h in stage_snap.items():
            stage = dict(kv.split("=", 1) for kv in key.split(","))["stage"]
            stages[stage] = {"flushes": int(h["count"]),
                             "sumS": round(h["sum"], 4),
                             "p99Ms": round(1000.0 * h.get("p99", 0.0), 3)}
        ab[arm_name] = {"probe": probe_recs, "rep": rep, "stages": stages,
                        "inFlightDepth": summary["pipeline"]["depth"]}
    assert ab["pipelined"]["probe"] == ab["serial"]["probe"], (
        "pipelined records diverged from serial on the probe slice")
    speedup = (ab["pipelined"]["rep"]["rowsPerSec"]
               / max(ab["serial"]["rep"]["rowsPerSec"], 1e-9))
    p99_ratio = (ab["pipelined"]["rep"]["p99Ms"]
                 / max(ab["serial"]["rep"]["p99Ms"], 1e-9))
    ab_cores = (len(os.sched_getaffinity(0))
                if hasattr(os, "sched_getaffinity")
                else (os.cpu_count() or 1))
    ab_gated = ab_cores >= 2
    min_speedup = float(os.environ.get("BENCH_PIPE_MIN_SPEEDUP", 1.3))
    max_p99_ratio = float(os.environ.get("BENCH_PIPE_MAX_P99_RATIO", 1.2))
    print(json.dumps({
        "metric": f"serve_pipeline_ab_speedup_{d}feat_{platform}",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "phases": {
            "depth": pipe_depth, "offeredRps": round(sat_rps, 1),
            "serialRowsPerSec": ab["serial"]["rep"]["rowsPerSec"],
            "pipelinedRowsPerSec": ab["pipelined"]["rep"]["rowsPerSec"],
            "serialP99Ms": ab["serial"]["rep"]["p99Ms"],
            "pipelinedP99Ms": ab["pipelined"]["rep"]["p99Ms"],
            "p99Ratio": round(p99_ratio, 3),
            "serialStages": ab["serial"]["stages"],
            "pipelinedStages": ab["pipelined"]["stages"],
            "probeBitEqual": True,
            "cores": ab_cores,
            "speedupGate": ("enforced" if ab_gated else
                            "skipped: single-core host"),
        },
    }), flush=True)
    if ab_gated:
        assert speedup >= min_speedup, (
            f"pipelined dataplane sustained only {speedup:.2f}x the "
            f"serial loop under saturation (gate: >= {min_speedup}x on "
            f"{ab_cores} cores)")
        assert p99_ratio <= max_p99_ratio, (
            f"pipelined p99 is {p99_ratio:.2f}x serial "
            f"(gate: <= {max_p99_ratio}x)")

    # the chaos soak's post-mortem bundles land in a bench-scoped dir so
    # the ≥1-valid-bundle assertion below reads a known-empty directory
    import shutil as _shutil
    import tempfile as _tempfile

    from transmogrifai_tpu.observability import blackbox as _blackbox
    from transmogrifai_tpu.observability import postmortem as _postmortem
    from transmogrifai_tpu.observability import timeseries as _timeseries
    pm_dir = _tempfile.mkdtemp(prefix="tg_bench_postmortems_")
    os.environ["TG_POSTMORTEM_DIR"] = pm_dir
    # SLO plane for the serve lines (docs/observability.md "SLOs,
    # budgets & burn rates"): fast sampling so the scaled alert windows
    # (page long = window/720 = 5s) hold several samples, a compressed
    # budget window, and a 0.99 availability target — the page alert
    # needs a sustained ≥14.4% bad fraction, which the clean line (zero
    # sheds expected) can never produce and the 2× chaos line (massive
    # overload shedding) always does: the zero-false-positive /
    # must-fire pair is asserted below
    slo_env = {"TG_SAMPLE_EVERY_S": "0.2", "TG_SLO_WINDOW_S": "3600",
               "TG_SLO_AVAILABILITY": "0.99"}
    saved_slo_env = {k: os.environ.get(k) for k in slo_env}
    os.environ.update(slo_env)
    # six lines: recorder-off baseline (TG_BLACKBOX=0) → ledger-off →
    # sampler-off (TG_SAMPLER=0: no windowed telemetry, no SLO trackers)
    # → clean (always-on flight recorder + ledger + sampler + SLO
    # engine; each overhead must stay ≤2% of its off line — asserted,
    # completion-ratio normalized like the round-9 watchdog gate) →
    # same load with the drift monitor folding every batch (≤5% of
    # clean — asserted) → chaos soak at 2× (must dump ≥1 schema-valid
    # post-mortem bundle, fire ≥1 page-severity SLO alert, and dump a
    # matching slo_budget_exhausted bundle — asserted;
    # docs/benchmarks.md rounds 11/13)
    clean_rows_per_sec = None
    lines = {}
    for arm in ("noblackbox", "noledger", "nosampler", "clean", "drift",
                "chaos2x"):
        faulted = arm == "chaos2x"
        rps = runtime_capacity * (2.0 if faulted else clean_frac)
        monitor = None
        amark = _obs_ledger.ledger().mark()
        if arm == "noblackbox":
            _blackbox.enable_blackbox(False)
        if arm == "noledger":
            # TG_LEDGER=0 reference arm: the clean line below must stay
            # within 2% of this (completion-ratio normalized — the same
            # gate shape as the round-11 recorder arm)
            _obs_ledger.enable_ledger(False)
        if arm == "nosampler":
            # TG_SAMPLER=0 reference arm: the clean line's sampler+SLO
            # overhead gate (≤2%, same normalization) reads this
            _timeseries.enable_sampler(False)
        if arm == "drift":
            from transmogrifai_tpu.serving.drift import (
                DriftBaseline, DriftMonitor)
            monitor = DriftMonitor(DriftBaseline.from_model(model))
        if faulted:
            # deterministic chaos at every serve site: admission faults, a
            # batching fault, and enough consecutive dispatch faults to
            # open the breaker (threshold 3) and exercise its probe
            faults.configure({
                "serve.enqueue": {"mode": "raise", "nth": 40, "count": 3,
                                  "transient": True},
                "serve.flush": {"mode": "raise", "nth": 2, "count": 1,
                                "transient": True},
                "serve.dispatch": {"mode": "raise", "nth": 3, "count": 5,
                                   "transient": True},
            })
        try:
            with ServingRuntime(model, f"bench-{arm}", cfg,
                                drift_monitor=monitor) as rt:
                rt.warm()
                rep = run_open_loop(rt, rows, seconds, rps,
                                    deadline_ms=deadline_ms)
                summary = rt.summary()
        finally:
            faults.clear()
            if arm == "noblackbox":
                _blackbox.enable_blackbox(None)
            if arm == "noledger":
                _obs_ledger.enable_ledger(None)
            if arm == "nosampler":
                _timeseries.enable_sampler(None)
        lines[arm] = rep
        suffix = "" if arm == "clean" else f"_{arm}"
        phases = {
            "scorerRowsPerSec": round(capacity, 1),
            "runtimeRowsPerSec": round(runtime_capacity, 1),
            "offeredRps": rep["offeredRps"],
            "p50Ms": rep["p50Ms"],
            "p99Ms": rep["p99Ms"],
            "shedOverload": rep["shedOverload"],
            "shedDeadline": rep["shedDeadline"],
            "submitErrors": rep["submitErrors"],
            "failed": rep["failed"],
            "degradedRows": rep["degradedRows"],
            "quarantined": rep["quarantined"],
            "breakerOpens": summary["breaker"]["opens"],
            "breakerState": summary["breaker"]["state"],
            **_ledger_phases(amark),
        }
        if arm == "clean":
            clean_rows_per_sec = rep["rowsPerSec"]
            # the ≤2% always-on recorder gate: same offered load as the
            # TG_BLACKBOX=0 line; normalize by completion ratio (the
            # open-loop generator's own pacing varies a few % run to
            # run — the round-9 watchdog-gate normalization)
            off = lines["noblackbox"]
            off_ratio = off["completed"] / max(off["offered"], 1)
            ratio = rep["completed"] / max(rep["offered"], 1)
            overhead = 1.0 - ratio / max(off_ratio, 1e-9)
            phases["blackboxOverheadVsOff"] = round(overhead, 4)
            phases["slowestRequests"] = rep["slowestRequests"]
            assert ratio >= 0.98 * off_ratio, (
                f"flight-recorder overhead {overhead:.1%} exceeds the "
                f"2% budget (clean {rep['completed']}/{rep['offered']} "
                f"vs off {off['completed']}/{off['offered']})")
            # the ≤2% compile-ledger gate: same load as the TG_LEDGER=0
            # arm, same completion-ratio normalization
            offl = lines["noledger"]
            offl_ratio = offl["completed"] / max(offl["offered"], 1)
            l_overhead = 1.0 - ratio / max(offl_ratio, 1e-9)
            phases["ledgerOverheadVsOff"] = round(l_overhead, 4)
            assert ratio >= 0.98 * offl_ratio, (
                f"compile-ledger overhead {l_overhead:.1%} exceeds the "
                f"2% budget (clean {rep['completed']}/{rep['offered']} "
                f"vs TG_LEDGER=0 {offl['completed']}/{offl['offered']})")
            # the ≤2% sampler+SLO gate: same load as the TG_SAMPLER=0
            # arm, same completion-ratio normalization (round 13)
            offs = lines["nosampler"]
            offs_ratio = offs["completed"] / max(offs["offered"], 1)
            s_overhead = 1.0 - ratio / max(offs_ratio, 1e-9)
            phases["samplerOverheadVsOff"] = round(s_overhead, 4)
            assert ratio >= 0.98 * offs_ratio, (
                f"sampler+SLO overhead {s_overhead:.1%} exceeds the "
                f"2% budget (clean {rep['completed']}/{rep['offered']} "
                f"vs TG_SAMPLER=0 {offs['completed']}/{offs['offered']})")
            # zero false positives: the clean line must not fire a
            # single page-severity burn-rate alert (the chaos line's
            # must-fire twin is asserted below)
            clean_page = _slo_page_fires(summary)
            phases["sloPageAlerts"] = clean_page
            assert clean_page == 0, (
                f"clean serve line fired {clean_page} page-severity SLO "
                f"alert(s) — burn-rate false positive")
        elif arm == "drift":
            # the ≤5% monitor-overhead acceptance gate: same offered
            # load as the clean line, every batch folded + verdicts on
            # the row cadence — sustained throughput must hold
            drift_snap = summary.get("drift") or {}
            phases["driftRowsFolded"] = drift_snap.get("rows", 0)
            phases["driftVerdict"] = drift_snap.get("verdict")
            overhead = 1.0 - rep["rowsPerSec"] / max(clean_rows_per_sec, 1e-9)
            phases["overheadVsClean"] = round(overhead, 4)
            assert rep["rowsPerSec"] >= 0.95 * clean_rows_per_sec, (
                f"drift monitor overhead {overhead:.1%} exceeds the 5% "
                f"budget ({rep['rowsPerSec']} vs clean "
                f"{clean_rows_per_sec} rows/sec)")
        elif faulted:
            # the chaos line's breaker opens are trigger events: ≥1
            # schema-valid post-mortem bundle must have been dumped
            bundles = _postmortem.list_bundles(pm_dir)
            assert bundles, "chaos soak produced no post-mortem bundle"
            docs = [_postmortem.read_bundle(p) for p in bundles]
            bad = [(p, _postmortem.validate_bundle(d))
                   for p, d in zip(bundles, docs)
                   if _postmortem.validate_bundle(d)]
            assert not bad, f"invalid post-mortem bundle(s): {bad}"
            phases["postmortemBundles"] = len(bundles)
            phases["postmortemTriggers"] = sorted(
                {d["trigger"]["kind"] for d in docs})
            # the must-fire twin of the clean line's zero-false-positive
            # gate: 2× overload sheds ~half the offered load, which must
            # page AND fully burn the availability budget — with the
            # matching slo_budget_exhausted bundle on disk (round 13)
            chaos_page = _slo_page_fires(summary)
            phases["sloPageAlerts"] = chaos_page
            assert chaos_page >= 1, (
                "chaos serve line fired no page-severity SLO alert "
                "despite 2x overload shedding")
            assert "slo_budget_exhausted" in phases["postmortemTriggers"], (
                f"chaos soak dumped no slo_budget_exhausted bundle "
                f"(triggers: {phases['postmortemTriggers']})")
            _shutil.rmtree(pm_dir, ignore_errors=True)
            os.environ.pop("TG_POSTMORTEM_DIR", None)
            for k, v in saved_slo_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        print(json.dumps({
            "metric": f"serve_rows_per_sec{suffix}_{d}feat_{platform}",
            "value": rep["rowsPerSec"],
            "unit": "rows/sec",
            # vs the saturated runtime capacity measured this run: the
            # clean line should sit near its offered 0.35×, the chaos line
            # shows what survives faults + 2× overload
            "vs_baseline": round(rep["rowsPerSec"] / runtime_capacity, 3),
            "phases": phases,
        }), flush=True)

    # ---- replica fleet lines (round 14; docs/serving.md "Replica fleet
    # & front door"): saturated rows/sec + p99 across 1→2→4 local
    # replicas behind the front door, then a kill-chaos soak asserting
    # the fleet invariant — zero lost requests + a replica_lost
    # post-mortem — with the warm-path zero-compile tripwire green on
    # EVERY replica before any line runs.
    import threading as _threading

    from transmogrifai_tpu.serving import FleetConfig, FrontDoor
    fleet_counts = [int(x) for x in os.environ.get(
        "BENCH_FLEET_REPLICAS", "1,2,4").split(",") if x.strip()]
    fleet_seconds = float(os.environ.get("BENCH_FLEET_SECONDS", seconds))
    fdir = _tempfile.mkdtemp(prefix="tg_bench_fleet_model_")
    fleet_pm = _tempfile.mkdtemp(prefix="tg_bench_fleet_pm_")
    os.environ["TG_POSTMORTEM_DIR"] = fleet_pm
    fleet_subproc = bool(int(os.environ.get("TG_FLEET_SUBPROCESS", "0")
                             or 0))
    try:
        model.save(fdir)  # populates <fdir>/programs at save (TG_AOT)
        fleet_lines = {}
        for nrep in fleet_counts:
            fc = FleetConfig(min_replicas=1, max_replicas=max(nrep, 1),
                             probe_interval_ms=200.0, autoscale=False,
                             subprocess=fleet_subproc)
            _pstore.close_sessions()
            amark = _obs_ledger.ledger().mark()
            with FrontDoor({"m": fdir}, replicas=nrep, config=cfg,
                           fleet_config=fc, warm=True) as fd:
                # warm tripwire, per replica: after every replica's
                # manifest-warm pre-pass, a real request through EACH
                # replica must record ZERO ledger compiles
                wmark = _obs_ledger.ledger().mark()
                for _rid, _rep in sorted(fd._replicas.items()):
                    _rep.submit("m", rows[0]).result(timeout=30)
                retraced = _obs_ledger.ledger().since(wmark)
                for r in retraced:
                    print(json.dumps(
                        {"fleetWarmViolation": r.to_json()}), flush=True)
                assert not retraced, (
                    f"fleet warm path retraced {len(retraced)} "
                    f"program(s) across {nrep} replica(s) — causes: "
                    f"{[r.cause for r in retraced]}")
                # AOT populate-once tripwire (round 15): with the store
                # populated at save, replicas 2..N must pay ZERO warm
                # compiles — at most ONE replica (none, when save
                # populated) compiles for the whole fleet. warm_info
                # crosses the subprocess protocol via health(), so the
                # same gate holds under TG_FLEET_SUBPROCESS.
                warm_reports = {
                    rid: (rep.warm_reports() or {}).get("m") or {}
                    for rid, rep in sorted(fd._replicas.items())}
                tail = list(sorted(warm_reports.items()))[1:]
                tail_compiles = sum(int(w.get("compiles", 0) or 0)
                                    for _rid, w in tail)
                assert tail_compiles == 0, (
                    f"replicas 2..{nrep} paid {tail_compiles} warm "
                    f"compile(s) — the program store did not share the "
                    f"first replica's programs: {warm_reports}")
                fleet_aot_hits = sum(int(w.get("aotHits", 0) or 0)
                                     for w in warm_reports.values())
                if not fleet_subproc:
                    # in-process replicas share this ledger: the WHOLE
                    # fleet bring-up (all N loads) must record zero
                    # builds when the store was populated at save
                    bringup = _obs_ledger.ledger().since(amark)
                    bringup = [r for r in bringup if r.seq <= wmark]
                    assert not bringup, (
                        f"fleet bring-up compiled {len(bringup)} "
                        f"program(s) despite a populated store — "
                        f"causes: {[r.cause for r in bringup]}")
                frep = run_open_loop(
                    fd, rows, fleet_seconds,
                    runtime_capacity * 1.2 * nrep,
                    deadline_ms=deadline_ms)
                assert frep["lost"] == 0 and frep["failed"] == 0, frep
                assert frep["accountingOk"], frep
            fleet_lines[nrep] = frep
            print(json.dumps({
                "metric": f"serve_fleet{nrep}_rows_per_sec_{d}feat_"
                          f"{platform}",
                "value": frep["rowsPerSec"],
                "unit": "rows/sec",
                "vs_baseline": round(
                    frep["rowsPerSec"] / runtime_capacity, 3),
                "phases": {
                    "replicas": nrep,
                    "offeredRps": frep["offeredRps"],
                    "p50Ms": frep["p50Ms"], "p99Ms": frep["p99Ms"],
                    "shedOverload": frep["shedOverload"],
                    "shedDeadline": frep["shedDeadline"],
                    "routing": frep["replicas"],
                    "failovers": frep["fleet"]["failovers"],
                    "aotWarmHits": fleet_aot_hits,
                    "subprocess": fleet_subproc,
                    **_ledger_phases(amark),
                },
            }), flush=True)
        if 1 in fleet_lines and 2 in fleet_lines:
            factor = (fleet_lines[2]["rowsPerSec"]
                      / max(fleet_lines[1]["rowsPerSec"], 1e-9))
            cores = (len(os.sched_getaffinity(0))
                     if hasattr(os, "sched_getaffinity")
                     else (os.cpu_count() or 1))
            # the 2-replica scaling gate needs real parallel hardware:
            # in-process replicas on a single-core host can only win on
            # queueing, never on compute — the gate is capability-skipped
            # there (same policy as the two-process CPU cluster test),
            # with the measured factor still printed. Round 19 floor:
            # with each replica's dataplane already pipelined, ×2 must
            # still clear ×1 by BENCH_FLEET_MIN_SCALING (default 1.05 —
            # replication may not double throughput in one process, but
            # it must never cost it)
            gated = cores >= 2
            min_scaling = float(os.environ.get(
                "BENCH_FLEET_MIN_SCALING", 1.05))
            print(json.dumps({
                "metric": f"serve_fleet_scaling_2v1_{platform}",
                "value": round(factor, 3),
                "unit": "x",
                "vs_baseline": round(factor, 3),
                "phases": {"cores": cores,
                           "minScaling": min_scaling,
                           "scalingGate": ("enforced" if gated else
                                           "skipped: single-core host")},
            }), flush=True)
            if gated:
                assert factor >= min_scaling, (
                    f"2-replica fleet line sustained only {factor:.2f}x "
                    f"the single-replica line (gate: >= {min_scaling}x "
                    f"on {cores} cores)")

        # kill-chaos fleet line: one replica murdered mid-soak; the run
        # must still account every request (zero lost, zero failed) and
        # leave >= 1 schema-valid replica_lost post-mortem bundle
        fc = FleetConfig(min_replicas=1, max_replicas=2,
                         probe_interval_ms=100.0, max_failovers=3,
                         autoscale=False, subprocess=fleet_subproc)
        with FrontDoor({"m": fdir}, replicas=2, config=cfg,
                       fleet_config=fc, warm=True) as fd:
            def _mid_soak_kill():
                active = [rid for rid, r in sorted(fd._replicas.items())
                          if r.state == "active"]
                if active:
                    fd.kill_replica(active[0])
            killer = _threading.Timer(fleet_seconds / 2.0,
                                      _mid_soak_kill)
            killer.daemon = True
            killer.start()
            try:
                krep = run_open_loop(fd, rows, fleet_seconds,
                                     runtime_capacity * 0.8,
                                     deadline_ms=deadline_ms)
            finally:
                killer.cancel()
            ksnap = fd.fleet_snapshot()
        assert krep["lost"] == 0 and krep["failed"] == 0, krep
        assert krep["accountingOk"], krep
        assert ksnap["kills"] >= 1, "kill timer never fired"
        kbundles = _postmortem.list_bundles(fleet_pm)
        kdocs = [_postmortem.read_bundle(p) for p in kbundles]
        lost_docs = [d for d in kdocs
                     if d["trigger"]["kind"] == "replica_lost"]
        assert lost_docs, (
            f"fleet kill soak dumped no replica_lost bundle "
            f"(triggers: {[d['trigger']['kind'] for d in kdocs]})")
        bad = [p for p, d in zip(kbundles, kdocs)
               if _postmortem.validate_bundle(d)]
        assert not bad, f"invalid post-mortem bundle(s): {bad}"
        print(json.dumps({
            "metric": f"serve_fleet_kill_rows_per_sec_{d}feat_"
                      f"{platform}",
            "value": krep["rowsPerSec"],
            "unit": "rows/sec",
            "vs_baseline": round(
                krep["rowsPerSec"] / runtime_capacity, 3),
            "phases": {
                "replicas": 2, "kills": ksnap["kills"],
                "failovers": ksnap["failovers"],
                "lost": krep["lost"], "failed": krep["failed"],
                "shedNoReplica": krep["shedNoReplica"],
                "shedOverload": krep["shedOverload"],
                "shedDeadline": krep["shedDeadline"],
                "routing": krep["replicas"],
                "postmortemBundles": len(kbundles),
                "postmortemTriggers": sorted(
                    {d["trigger"]["kind"] for d in kdocs}),
            },
        }), flush=True)
    finally:
        _shutil.rmtree(fdir, ignore_errors=True)
        _shutil.rmtree(fleet_pm, ignore_errors=True)
        os.environ.pop("TG_POSTMORTEM_DIR", None)

    # network-edge wire lines (docs/serving.md "Network edge"): the same
    # clean open-loop rate over real localhost sockets, one line per
    # framing, against an in-process reference on the SAME runtime at
    # the SAME rate — protocol overhead is a measured, gated number.
    # Then a disconnect-chaos arm: forced net.read/net.write drops plus
    # a reconnect mix, asserting the wire accounting identity (zero
    # lost futures, zero untyped failures, disconnects land in the
    # typed shedDisconnect bucket).
    from transmogrifai_tpu.serving.loadgen import run_wire_open_loop
    from transmogrifai_tpu.serving.netedge import NetEdge
    wire_seconds = float(os.environ.get("BENCH_WIRE_SECONDS", seconds))
    wire_rps = max(10.0, runtime_capacity
                   * float(os.environ.get("BENCH_SERVE_CLEAN_FRACTION",
                                          0.35)))
    min_frac = float(os.environ.get("BENCH_WIRE_MIN_FRACTION", 0.5))
    # batched requests are the columnar framing's natural shape; 1-row
    # requests over a handful of synchronous connections would measure
    # client round-trip latency, not the edge
    wire_batch = int(os.environ.get("BENCH_WIRE_BATCH_ROWS", 32))
    with ServingRuntime(model, "wire", cfg) as rt:
        rt.warm()
        inproc = run_open_loop(rt, rows, wire_seconds, wire_rps,
                               deadline_ms=deadline_ms)
        with NetEdge(rt, name="bench") as edge:
            whost, wport = edge.address
            for proto in ("http", "binary"):
                wrep = run_wire_open_loop(
                    whost, wport, rows, wire_seconds, wire_rps,
                    deadline_ms=deadline_ms, protocols=(proto,),
                    batch_rows=wire_batch)
                assert wrep["lost"] == 0 and wrep["failed"] == 0, wrep
                assert wrep["accountingOk"], wrep
                ratio = (wrep["rowsPerSec"]
                         / max(inproc["rowsPerSec"], 1.0))
                if proto == "binary":
                    # the fast-path gate: binary framing must sustain at
                    # least BENCH_WIRE_MIN_FRACTION of the in-process
                    # line at the same offered rate
                    assert ratio >= min_frac, (
                        f"binary wire line sustained only "
                        f"{wrep['rowsPerSec']:.1f} rows/s vs "
                        f"{inproc['rowsPerSec']:.1f} in-process "
                        f"(ratio {ratio:.3f} < gate {min_frac})")
                pp = wrep["protocols"][proto]
                print(json.dumps({
                    "metric": f"serve_wire_{proto}_rows_per_sec_"
                              f"{d}feat_{platform}",
                    "value": wrep["rowsPerSec"],
                    "unit": "rows/sec",
                    "vs_baseline": round(ratio, 3),
                    "phases": {
                        "inProcessRowsPerSec": inproc["rowsPerSec"],
                        "wireOverheadPct": round(100.0 * (1.0 - ratio),
                                                 1),
                        "batchRows": wire_batch,
                        "offeredRps": wrep["offeredRps"],
                        "p50Ms": pp["p50Ms"], "p99Ms": pp["p99Ms"],
                        "lost": wrep["lost"], "failed": wrep["failed"],
                        "shedOverload": wrep["shedOverload"],
                        "shedDisconnect": wrep["shedDisconnect"],
                    },
                }), flush=True)
            # disconnect-chaos arm: drop a handful of connections at the
            # read and write sites mid-soak while the driver also churns
            # connections (reconnect_every) — the identity must hold
            with faults.injected({
                    "net.read": {"mode": "raise", "nth": 5, "count": 3},
                    "net.write": {"mode": "raise", "nth": 9,
                                  "count": 3}}):
                crep = run_wire_open_loop(
                    whost, wport, rows, wire_seconds, wire_rps,
                    deadline_ms=deadline_ms,
                    protocols=("http", "binary"), reconnect_every=7,
                    batch_rows=wire_batch)
            assert crep["lost"] == 0 and crep["failed"] == 0, crep
            assert crep["accountingOk"], crep
            assert crep["shedDisconnect"] >= 1, (
                f"disconnect chaos armed but no shedDisconnect: {crep}")
            print(json.dumps({
                "metric": f"serve_wire_chaos_rows_per_sec_"
                          f"{d}feat_{platform}",
                "value": crep["rowsPerSec"],
                "unit": "rows/sec",
                "vs_baseline": round(
                    crep["rowsPerSec"]
                    / max(inproc["rowsPerSec"], 1.0), 3),
                "phases": {
                    "shedDisconnect": crep["shedDisconnect"],
                    "shedOverload": crep["shedOverload"],
                    "lost": crep["lost"], "failed": crep["failed"],
                    "accountingOk": crep["accountingOk"],
                    "p99Ms": crep["p99Ms"],
                },
            }), flush=True)

    # multi-model density lines (round 17; docs/serving.md "Multi-model
    # placement & paging"): M models bin-packed onto 2 replicas under a
    # warm bound, a uniform per-model traffic mix, and the per-model
    # accounting identity gated. Then a warm-copy kill arm: murder the
    # replica holding the ONLY warm copy of one model mid-soak — the
    # model must page in on a survivor (an AOT deserialize, never a
    # compile), records stay bit-equal, and zero futures are lost.
    from transmogrifai_tpu.serving import PlaceConfig
    n_models = int(os.environ.get("BENCH_DENSITY_MODELS", 3))
    ddir = _tempfile.mkdtemp(prefix="tg_bench_density_model_")
    try:
        model.save(ddir)  # one artifact, M logical models: the density
        # line measures placement/paging, not M distinct fits
        dmodels = {f"m{i}": ddir for i in range(n_models)}
        mix = [(m, 1.0) for m in sorted(dmodels)]
        # max_warm=1 on 2 replicas: fleet-wide warm capacity (2) is
        # BELOW the catalog (N models, N >= 3) — the clean arm itself
        # must demand-page, which is the density point
        pc = PlaceConfig(max_warm=1)
        fc = FleetConfig(min_replicas=1, max_replicas=2,
                         probe_interval_ms=200.0, max_failovers=3,
                         autoscale=False, subprocess=fleet_subproc)
        _pstore.close_sessions()
        with FrontDoor(dmodels, replicas=2, config=cfg,
                       fleet_config=fc, warm=True, placement=pc) as fd:
            drep = run_open_loop(fd, rows, fleet_seconds,
                                 runtime_capacity * 0.8,
                                 deadline_ms=deadline_ms, models=mix)
            dsummary = fd.summary()
            dplace = fd.fleet_snapshot()["placement"]
        assert drep["lost"] == 0 and drep["failed"] == 0, drep
        assert drep["accountingOk"], drep
        per = drep["models"] or {}
        assert sum(b["offered"] for b in per.values()) == \
            drep["offered"], per
        assert sum(b["completed"] for b in per.values()) == \
            drep["completed"], per
        assert dplace["pageIns"] >= 1, (
            f"density clean arm paged nothing in despite "
            f"{n_models} models over 2 warm slots: {dplace}")
        assert dplace["pageInP99Ms"] is not None, dplace
        # zero cross-model SLO page alerts on the clean arm: typed
        # paging sheds must not burn a co-resident model's budget to
        # the page line
        dpage = _slo_page_fires(dsummary)
        assert dpage == 0, (
            f"density clean arm fired {dpage} page-severity SLO "
            f"alert(s)")
        print(json.dumps({
            "metric": f"serve_density{n_models}m_rows_per_sec_"
                      f"{d}feat_{platform}",
            "value": drep["rowsPerSec"],
            "unit": "rows/sec",
            "vs_baseline": round(
                drep["rowsPerSec"] / runtime_capacity, 3),
            "phases": {
                "models": n_models, "replicas": 2,
                "maxWarm": pc.max_warm,
                "offeredRps": drep["offeredRps"],
                "p50Ms": drep["p50Ms"], "p99Ms": drep["p99Ms"],
                "perModelOffered": {m: b["offered"]
                                    for m, b in sorted(per.items())},
                "resident": dplace["resident"],
                "pageIns": dplace["pageIns"],
                "evictions": dplace["evictions"],
                "pageInP99Ms": dplace["pageInP99Ms"],
                "sloPageAlerts": dpage,
                "lost": drep["lost"], "failed": drep["failed"],
            },
        }), flush=True)

        _pstore.close_sessions()
        with FrontDoor(dmodels, replicas=2, config=cfg,
                       fleet_config=fc, warm=True, placement=pc) as fd:
            lone = next(m for m in sorted(dmodels)
                        if len(fd.placer.holders(m)) == 1)
            victim = fd.placer.holders(lone)[0]
            dbaseline = mb(rows[:8])

            def _kill_lone_holder():
                fd.kill_replica(victim)
            killer = _threading.Timer(fleet_seconds / 2.0,
                                      _kill_lone_holder)
            killer.daemon = True
            killer.start()
            try:
                dkrep = run_open_loop(fd, rows, fleet_seconds,
                                      runtime_capacity * 0.6,
                                      deadline_ms=deadline_ms,
                                      models=mix)
            finally:
                killer.cancel()
            # the orphaned model paged in on a survivor: warm again,
            # and bit-equal to the in-process scorer. The survivor may
            # sit ejected for a few probe cycles right after the soak
            # (overload made it un-ready) — wait out readmission; the
            # retries are typed sheds, not failures
            from transmogrifai_tpu.serving import OverloadError
            retry_until = time.perf_counter() + 30.0
            while True:
                try:
                    drecs = [fd.submit(r, model=lone).result(timeout=30)
                             for r in rows[:8]]
                    break
                except OverloadError:
                    if time.perf_counter() > retry_until:
                        raise
                    time.sleep(0.25)
            assert drecs == dbaseline, (
                f"density kill arm: model '{lone}' records diverged "
                f"after paging in on a survivor")
            dksnap = fd.fleet_snapshot()
            dkinds = {r.kind for r in fd.fault_log.reports}
        assert dkrep["lost"] == 0 and dkrep["failed"] == 0, dkrep
        assert dkrep["accountingOk"], dkrep
        assert dksnap["kills"] >= 1, "density kill timer never fired"
        assert "replica_lost" in dkinds, dkinds
        assert "placement_paged_in" in dkinds, (
            f"killing {lone}'s only warm copy triggered no page-in: "
            f"{sorted(dkinds)}")
        dkplace = dksnap["placement"]
        print(json.dumps({
            "metric": f"serve_density{n_models}m_kill_rows_per_sec_"
                      f"{d}feat_{platform}",
            "value": dkrep["rowsPerSec"],
            "unit": "rows/sec",
            "vs_baseline": round(
                dkrep["rowsPerSec"] / runtime_capacity, 3),
            "phases": {
                "models": n_models, "replicas": 2,
                "killedReplica": victim, "orphanedModel": lone,
                "kills": dksnap["kills"],
                "failovers": dksnap["failovers"],
                "pageIns": dkplace["pageIns"],
                "evictions": dkplace["evictions"],
                "pageInP99Ms": dkplace["pageInP99Ms"],
                "resident": dkplace["resident"],
                "shedNoReplica": dkrep["shedNoReplica"],
                "shedOverload": dkrep["shedOverload"],
                "shedDeadline": dkrep["shedDeadline"],
                "lost": dkrep["lost"], "failed": dkrep["failed"],
            },
        }), flush=True)
    finally:
        _shutil.rmtree(ddir, ignore_errors=True)


def _run_stream(platform):
    """BENCH_MODE=stream: out-of-core input-engine A/B (docs/streaming.md).
    Three arms train the SAME vectorize → sanity-check → streaming-GBT
    pipeline (num_trees=2, max_depth=3 → 11 prep/grow passes over a
    BENCH_STREAM_ROWS × BENCH_STREAM_FEATURES synthetic source, default
    1M × 64, regenerated deterministically per pass, never materialized):

      serial          TG_STREAM_WORKERS=1, prefetch 1, cache off
      parallel        worker pool (4), prefetch 4, cache off
      parallel+cache  worker pool + host transformed-chunk cache sized to
                      hold the working set (passes ≥2 replay from RAM)

    Per arm: rows/sec, read/transform/upload stage seconds, overlap
    fraction, uploaded bytes, cache hit rate, and the O(chunk) residency
    bound asserted at that arm's prefetch. Across arms: the fitted models
    must score bit-identically (the engine is an optimization, not a
    semantic change), and on ≥2 cores the pinned tripwires hold —
    parallel ≥ serial throughput, cached-arm upload bytes cut ≥3×."""
    import numpy as np
    import transmogrifai_tpu as tg
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.streaming import (
        SyntheticChunkSource, StreamingGBT, env_chunk_rows)
    from transmogrifai_tpu.workflow import OpWorkflow

    n = int(os.environ.get("BENCH_STREAM_ROWS", 1_000_000))
    d = int(os.environ.get("BENCH_STREAM_FEATURES", 64))
    chunk_rows = env_chunk_rows()
    source = SyntheticChunkSource(n, d, chunk_rows=chunk_rows, seed=0,
                                  problem="binary")
    probe = source.read_chunk(0).table
    # cache sized to hold every transformed chunk (raw + vectorized +
    # masks ≈ a few × raw float bytes) so passes ≥2 are pure host replays
    cache_fit_bytes = max(1 << 28, 6 * n * d * 4)
    arms = [
        ("serial", {"TG_STREAM_WORKERS": "1", "TG_STREAM_PREFETCH": "1",
                    "TG_STREAM_CACHE_BYTES": "0"}, 1),
        ("parallel", {"TG_STREAM_WORKERS": "4", "TG_STREAM_PREFETCH": "4",
                      "TG_STREAM_CACHE_BYTES": "0"}, 4),
        ("parallel_cache",
         {"TG_STREAM_WORKERS": "4", "TG_STREAM_PREFETCH": "4",
          "TG_STREAM_CACHE_BYTES": str(cache_fit_bytes)}, 4),
    ]
    results = {}
    keys = ("TG_STREAM_WORKERS", "TG_STREAM_PREFETCH",
            "TG_STREAM_CACHE_BYTES")
    saved = {k: os.environ.get(k) for k in keys}
    try:
        for arm, env, prefetch in arms:
            os.environ.update(env)
            label = FeatureBuilder.RealNN("y").extract_field().as_response()
            feats = [FeatureBuilder.Real(f"x{i}").extract_field()
                     .as_predictor() for i in range(d)]
            checked = label.transform_with(SanityChecker(seed=1),
                                           tg.transmogrify(feats))
            pred = (StreamingGBT(problem="binary", num_trees=2, max_depth=3,
                                 n_bins=32, learning_rate=1.0)
                    .set_input(label, checked).get_output())
            wf = OpWorkflow().set_result_features(pred)
            smark = _ledger_mark()
            t0 = time.perf_counter()
            model = wf.train(stream=source)
            wall = time.perf_counter() - t0
            stats = model.summary()["streaming"]
            pf = [f for f in model.result_features][0]
            scored = np.asarray(model.score(table=probe)[pf.name].values)
            # the O(chunk)-not-O(dataset) claim at THIS arm's prefetch:
            # at most prefetch+1 transformed chunks resident at once
            assert (stats["peakDeviceBytes"]
                    <= (prefetch + 1) * stats["maxChunkBytes"]), (arm, stats)
            assert stats["peakResidentChunks"] <= prefetch + 1, (arm, stats)
            if n * d * 4 >= 40 * stats["maxChunkBytes"]:
                # ...and a vanishing fraction of the raw dataset bytes
                # (meaningless at toy sizes where one chunk ≈ the dataset)
                assert stats["peakDeviceBytes"] <= (n * d * 4) / 4, (arm,
                                                                    stats)
            results[arm] = {"wall": wall, "stats": stats, "smark": smark,
                            "scored": scored.tobytes()}
            print(json.dumps({
                "metric": f"stream_train_rows_per_sec_{arm}_{n}rows_"
                          f"{d}feat_{platform}",
                "value": round(n / wall, 1),
                "unit": "rows/sec",
                # vs in-core is not meaningful (in-core cannot hold the
                # table); report the read/transform↔upload overlap instead
                "vs_baseline": round(stats["overlapFraction"], 3),
                "phases": {
                    "wallSecs": round(wall, 2),
                    "passes": round(stats["rows"] / max(n, 1), 2),
                    "chunks": stats["chunks"],
                    "chunkRows": chunk_rows,
                    "uploadBytes": stats["uploadBytes"],
                    **_ledger_phases(smark),
                    "maxChunkBytes": stats["maxChunkBytes"],
                    "peakDeviceBytes": stats["peakDeviceBytes"],
                    "peakResidentChunks": stats["peakResidentChunks"],
                    "overlapFraction": stats["overlapFraction"],
                    "readSeconds": stats["readSeconds"],
                    "transformSeconds": stats["transformSeconds"],
                    "uploadSeconds": stats["uploadSeconds"],
                    "waitSeconds": stats["waitSeconds"],
                    "cacheHitRate": stats.get("cache", {}).get("hitRate", 0.0),
                },
            }), flush=True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    # bit-equality across arms — always, at any core count: the pool and
    # the cache must not change a single scored byte
    assert results["parallel"]["scored"] == results["serial"]["scored"]
    assert results["parallel_cache"]["scored"] == results["serial"]["scored"]
    cached = results["parallel_cache"]["stats"]
    # the cache really absorbed passes ≥2: hits ≥ all chunks after pass 1
    assert cached["cacheHits"] > 0, cached
    assert cached["uploadBytes"] < results["parallel"]["stats"]["uploadBytes"]
    cores = os.cpu_count() or 1
    if cores >= 2:
        # pinned tripwires (multicore only — a 1-core host serializes the
        # pool and proves nothing about overlap)
        assert results["parallel"]["wall"] <= results["serial"]["wall"] * 1.05, \
            {a: round(r["wall"], 2) for a, r in results.items()}
        assert (cached["uploadBytes"] * 3
                <= results["parallel"]["stats"]["uploadBytes"]), cached
    print(json.dumps({
        "metric": f"stream_ab_speedup_{n}rows_{d}feat_{platform}",
        "value": round(results["serial"]["wall"]
                       / max(results["parallel_cache"]["wall"], 1e-9), 3),
        "unit": "x_serial_wall",
        "vs_baseline": round(results["serial"]["wall"]
                             / max(results["parallel"]["wall"], 1e-9), 3),
        "phases": {
            "serialWallSecs": round(results["serial"]["wall"], 2),
            "parallelWallSecs": round(results["parallel"]["wall"], 2),
            "cachedWallSecs": round(results["parallel_cache"]["wall"], 2),
            "uploadBytesSerial": results["serial"]["stats"]["uploadBytes"],
            "uploadBytesParallel":
                results["parallel"]["stats"]["uploadBytes"],
            "uploadBytesCached": cached["uploadBytes"],
            "cacheHitRate": cached.get("cache", {}).get("hitRate", 0.0),
            "cores": cores,
        },
    }), flush=True)


def _run_pressure(platform):
    """BENCH_MODE=pressure: forced ``oom.*`` at every choke point must
    complete end-to-end (bit-equal plan/serve results, identical sweep
    winner, finished stream train, zero failed serving requests), and the
    unforced watchdog+monitor overhead must stay ≤2% of the clean serve
    and stream lines (measured against TG_WATCHDOG_S=0)."""
    import jax.numpy as jnp
    import transmogrifai_tpu as tg_pkg
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.preparators.sanity_checker import SanityChecker
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.local import micro_batch_score_function
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    from transmogrifai_tpu.robustness import faults
    from transmogrifai_tpu.serving import ServeConfig, ServingRuntime
    from transmogrifai_tpu.serving.loadgen import run_open_loop, synthetic_rows
    from transmogrifai_tpu.streaming import StreamingGBT, TableChunkSource
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import Real, RealNN
    from transmogrifai_tpu.workflow import OpWorkflow

    model = _serve_model(int(os.environ.get("BENCH_SERVE_FIT_ROWS", 4000)),
                         int(os.environ.get("BENCH_SERVE_FEATURES", 16)))

    # -- forced oom.plan: bisected planned score must be bit-equal ----------
    mb = micro_batch_score_function(model)
    rows1k = synthetic_rows(model, 1024, seed=1)
    clean_recs = mb(rows1k)
    with faults.injected({"oom.plan": {"mode": "oom", "nth": 1}}):
        forced_recs = micro_batch_score_function(model)(rows1k)
    assert forced_recs == clean_recs, "oom.plan bisect changed results"

    # -- forced oom.sweep: split grid must elect the identical winner -------
    rng = np.random.RandomState(0)
    Xs = rng.randn(4096, 16).astype(np.float32)
    ys = (Xs @ rng.randn(16).astype(np.float32) > 0).astype(np.float32)
    grid = [{"regParam": r, "elasticNetParam": e}
            for r in (0.001, 0.01, 0.1, 0.3) for e in (0.0, 0.5)]
    sweep_models = [(MODEL_REGISTRY["OpLogisticRegression"], grid)]
    Xd, yd = jnp.asarray(Xs), jnp.asarray(ys)
    best_clean = OpCrossValidation(num_folds=3, seed=0).validate(
        sweep_models, Xd, yd, "binary", "AuROC", True, 2)
    with faults.injected({"oom.sweep": {"mode": "oom", "nth": 1,
                                        "count": 2}}):
        best_forced = OpCrossValidation(num_folds=3, seed=0).validate(
            sweep_models, Xd, yd, "binary", "AuROC", True, 2)
    assert (best_forced.family_name, best_forced.hyper,
            best_forced.metric_value) == (
        best_clean.family_name, best_clean.hyper,
        best_clean.metric_value), "oom.sweep split changed the winner"

    # -- serve lines: watchdog-off / clean / forced-oom ---------------------
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", 3.0))
    max_batch = int(os.environ.get("TG_SERVE_MAX_BATCH", 256))
    rows = synthetic_rows(model, 1024, seed=1)
    cfg = ServeConfig.from_env()
    cfg.max_batch = max_batch
    cfg.max_queue = int(os.environ.get("TG_SERVE_QUEUE_MAX", 512))
    batch = rows[:max_batch]
    mb(batch)
    t0 = time.perf_counter()
    for _ in range(3):
        mb(batch)
    capacity = 3 * len(batch) / (time.perf_counter() - t0)
    with ServingRuntime(model, "calibrate", cfg) as rt:
        rt.warm()
        cal = run_open_loop(rt, rows, min(1.5, seconds), capacity)
    runtime_capacity = max(cal["rowsPerSec"], 1.0)
    offered = runtime_capacity * float(
        os.environ.get("BENCH_SERVE_CLEAN_FRACTION", 0.35))

    prev_wd = os.environ.get("TG_WATCHDOG_S")
    serve_lines = {}
    for arm in ("watchdog_off", "clean", "oom"):
        amark = _ledger_mark()
        if arm == "watchdog_off":
            os.environ["TG_WATCHDOG_S"] = "0"
        elif prev_wd is None:
            os.environ.pop("TG_WATCHDOG_S", None)
        else:
            os.environ["TG_WATCHDOG_S"] = prev_wd
        if arm == "oom":
            # a pressure burst: 7 consecutive dispatch attempts exhaust —
            # the flush that hits it splits ~3 levels deep (each split
            # retries through the armed window) before the device
            # "recovers"; later flushes run clean
            faults.configure({"oom.serve": {"mode": "oom", "nth": 2,
                                            "count": 7}})
        try:
            with ServingRuntime(model, f"pressure-{arm}", cfg) as rt:
                rt.warm()
                rep = run_open_loop(rt, rows, seconds, offered)
                summary = rt.summary()
        finally:
            faults.clear()
        serve_lines[arm] = rep
        phases = {
            "offeredRps": rep["offeredRps"], "p50Ms": rep["p50Ms"],
            "p99Ms": rep["p99Ms"], "failed": rep["failed"],
            "shedOverload": rep["shedOverload"],
            "shedDeadline": rep["shedDeadline"],
            "oomDownshifts": summary["faults"]["oomDownshifts"],
            "threadStalls": summary["faults"]["threadStalls"],
            "breakerOpens": summary["breaker"]["opens"],
            **_ledger_phases(amark),
        }
        if arm == "clean":
            # normalize by the offered rate: the open-loop generator's
            # own pacing varies a few % run-to-run, so the honest
            # overhead measure is the completion ratio (completed /
            # offered), which both arms must hold at ~1.0
            off = serve_lines["watchdog_off"]
            off_ratio = off["completed"] / max(off["offered"], 1)
            ratio = rep["completed"] / max(rep["offered"], 1)
            overhead = 1.0 - ratio / max(off_ratio, 1e-9)
            phases["watchdogOverheadVsOff"] = round(overhead, 4)
            assert ratio >= 0.98 * off_ratio, (
                f"watchdog overhead {overhead:.1%} exceeds the 2% budget")
        if arm == "oom":
            assert rep["failed"] == 0 and rep["submitErrors"] == 0, rep
            assert summary["faults"]["oomDownshifts"] >= 1, summary
            assert summary["breaker"]["opens"] == 0, summary["breaker"]
            loss = 1.0 - rep["rowsPerSec"] / max(
                serve_lines["clean"]["rowsPerSec"], 1e-9)
            phases["throughputLossVsClean"] = round(loss, 4)
            assert rep["rowsPerSec"] >= 0.5 * serve_lines["clean"][
                "rowsPerSec"], "unbounded throughput loss under oom chaos"
        print(json.dumps({
            "metric": f"pressure_serve_rows_per_sec_{arm}_{platform}",
            "value": rep["rowsPerSec"],
            "unit": "rows/sec",
            "vs_baseline": round(rep["rowsPerSec"] / runtime_capacity, 3),
            "phases": phases,
        }), flush=True)

    # -- stream lines: watchdog-off / clean walls + forced oom.stream -------
    n = int(os.environ.get("BENCH_PRESSURE_STREAM_ROWS", 200_000))
    d = int(os.environ.get("BENCH_PRESSURE_STREAM_FEATURES", 8))
    chunk_rows = int(os.environ.get("BENCH_PRESSURE_CHUNK_ROWS", 25_000))
    rng = np.random.RandomState(0)
    Xs = rng.randn(n, d).astype(np.float32)
    ys = (Xs @ rng.randn(d).astype(np.float32) > 0).astype(np.float32)
    cols = {f"x{i}": Column(Real, Xs[:, i], None) for i in range(d)}
    cols["y"] = Column(RealNN, ys, None)
    table = FeatureTable(cols, n)

    def stream_train():
        label = FeatureBuilder.RealNN("y").extract_field().as_response()
        feats = [FeatureBuilder.Real(f"x{i}").extract_field().as_predictor()
                 for i in range(d)]
        checked = label.transform_with(SanityChecker(seed=1),
                                       tg_pkg.transmogrify(feats))
        pred = (StreamingGBT(problem="binary", num_trees=1, max_depth=3,
                             n_bins=16, learning_rate=1.0)
                .set_input(label, checked).get_output())
        src = TableChunkSource(table, chunk_rows=chunk_rows)
        t0 = time.perf_counter()
        m = OpWorkflow().set_result_features(pred).train(stream=src)
        return time.perf_counter() - t0, m

    walls = {}
    for arm in ("watchdog_off", "clean"):
        if arm == "watchdog_off":
            os.environ["TG_WATCHDOG_S"] = "0"
        elif prev_wd is None:
            os.environ.pop("TG_WATCHDOG_S", None)
        else:
            os.environ["TG_WATCHDOG_S"] = prev_wd
        walls[arm] = min(stream_train()[0] for _ in range(3))
    overhead = 1.0 - walls["watchdog_off"] / max(walls["clean"], 1e-9)
    assert walls["clean"] <= 1.02 * walls["watchdog_off"], (
        f"stream watchdog overhead {overhead:.1%} exceeds the 2% budget")
    pstream_mark = _ledger_mark()
    with faults.injected({"oom.stream": {"mode": "oom", "nth": 2}}):
        oom_wall, oom_model = stream_train()
    downshifts = oom_model.summary()["faults"]["oomDownshifts"]
    assert downshifts, "forced oom.stream produced no downshift"
    for arm, wall in (("watchdog_off", walls["watchdog_off"]),
                      ("clean", walls["clean"]), ("oom", oom_wall)):
        print(json.dumps({
            "metric": f"pressure_stream_rows_per_sec_{arm}_{n}rows_"
                      f"{d}feat_{platform}",
            "value": round(n / wall, 1),
            "unit": "rows/sec",
            "vs_baseline": round(walls["watchdog_off"] / wall, 3),
            # the oom line's ledger block shows the downshifted pass as a
            # bucket-change rebuild (chunk-budget halving re-chunks it)
            "phases": ({"wallSecs": round(wall, 3)} if arm != "oom" else
                       {"wallSecs": round(wall, 3),
                        "oomDownshifts": len(downshifts),
                        "downshiftChunkRows": downshifts[0]["detail"]
                        .get("chunkRows"),
                        **_ledger_phases(pstream_mark)}),
        }), flush=True)
    if prev_wd is None:
        os.environ.pop("TG_WATCHDOG_S", None)
    else:
        os.environ["TG_WATCHDOG_S"] = prev_wd


def _run_campaign(platform):
    """BENCH_MODE=campaign: the seeded fixed-budget chaos soak
    (docs/robustness.md "Chaos campaigns"). Runs BENCH_CAMPAIGN_SCHEDULES
    randomized multi-fault schedules (default 200; coverage singletons
    for every registered site first — the fleet.* sites included, so the
    site-coverage guard extends to the replica front door automatically)
    across all eight scenario harnesses (the ``net`` scenario drives the
    socket edge, so the ``net.*`` sites are covered over real
    connections)
    and asserts the campaign contract: 100% site coverage, ZERO invariant
    violations, and full serve request accounting (zero lost / zero
    failed futures). A violation prints the minimized one-command
    reproducer before failing — a bench failure is a repro, not a flaky
    soak."""
    from transmogrifai_tpu.robustness.campaign import ChaosCampaign
    from transmogrifai_tpu.robustness.faults import ALL_SITES

    n = int(os.environ.get("BENCH_CAMPAIGN_SCHEDULES", 200))
    seed = int(os.environ.get("BENCH_CAMPAIGN_SEED", 0))
    eng = ChaosCampaign(seed=seed)
    cmark = _ledger_mark()
    try:
        t0 = time.perf_counter()
        report = eng.run(count=n)
        wall = time.perf_counter() - t0
    finally:
        eng.close()
    doc = report.to_json()
    if doc["violations"]:
        print(json.dumps({"violations": doc["violations"]}, indent=2,
                         default=str), flush=True)
    assert not doc["violations"], (
        f"{len(doc['violations'])} invariant violation(s); minimized "
        f"repro(s): {[v.get('repro', {}).get('cmd') for v in doc['violations']]}")
    assert not doc["uncovered"], (
        f"campaign left {doc['uncovered']} of {len(ALL_SITES)} sites "
        f"unfired (coverage {doc['coveragePct']}%)")
    acct = doc["accounting"]
    assert acct["lost"] == 0 and acct["failed"] == 0, acct
    assert acct["submitted"] == (acct["completed"] + acct["shed"]), acct
    outcomes = {}
    for r in doc["results"]:
        key = r["outcome"].split(":")[0]
        outcomes[key] = outcomes.get(key, 0) + 1
    print(json.dumps({
        "metric": f"campaign_schedules_per_sec_{len(ALL_SITES)}sites_"
                  f"{platform}",
        "value": round(len(doc["results"]) / wall, 2),
        "unit": "schedules/sec",
        # vs_baseline here is the campaign verdict, not a speed ratio:
        # 1.0 = full coverage + zero violations
        "vs_baseline": 1.0 if (not doc["violations"]
                               and not doc["uncovered"]) else 0.0,
        "phases": {
            "wallSecs": round(wall, 2),
            "schedules": len(doc["results"]),
            "sites": doc["sites"],
            "coveragePct": doc["coveragePct"],
            "violations": len(doc["violations"]),
            "outcomes": outcomes,
            "firedTotal": sum(doc["firedBySite"].values()),
            "accounting": acct,
            **_ledger_phases(cmark),
        },
    }), flush=True)


def main():
    import jax
    import jax.numpy as jnp
    import transmogrifai_tpu.models.linear  # noqa: F401
    import transmogrifai_tpu.models.trees   # noqa: F401

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # every line is a device measurement: a CPU run under the same
        # metric names is not one (CPU correctness lives in tests/)
        raise SystemExit(
            f"bench.py measures the chip and found none (jax platform is "
            f"{platform!r})")
    mode = os.environ.get("BENCH_MODE", "both")
    n = int(os.environ.get("BENCH_ROWS", 1_000_000))
    d = int(os.environ.get("BENCH_FEATURES", 64))
    folds = 3
    reps = int(os.environ.get("BENCH_REPS", 5))

    if mode == "transform":
        _run_transform_ab(n, d, platform, reps)
        return
    if mode == "serve":
        _run_serve(platform)
        return
    if mode == "stream":
        _run_stream(platform)
        return
    if mode == "pressure":
        _run_pressure(platform)
        return
    if mode == "campaign":
        _run_campaign(platform)
        return
    if mode == "sweep":
        _run_sweep_line(platform, folds, reps)
        return

    rng = np.random.RandomState(0)
    X = rng.randn(n, d).astype(np.float32)
    w_true = rng.randn(d).astype(np.float32)
    y = (X @ w_true + rng.randn(n) > 0).astype(np.float32)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)

    # "both": default (out-of-the-box grids) first, dense LAST so the
    # final line remains the headline throughput number
    modes = ("default", "dense") if mode == "both" else (mode,)
    for m in modes:
        _run_mode(m, Xd, yd, n, d, platform, folds, reps)


if __name__ == "__main__":
    main()
