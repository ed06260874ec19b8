"""Transform-plan compiler: fused-vs-eager equivalence, dispatch counts,
packed uploads, cache bounds, and the chaos fallback contract (docs/plan.md).

The bit-exactness suite drives the three helloworld-parity example DAGs
(titanic / iris / boston feature definitions from
``transmogrifai_tpu/examples``) over synthetic data shaped like the real
datasets — the planned path must produce byte-identical values AND validity
masks to eager per-stage dispatch, train and score."""
import numpy as np
import pandas as pd
import pytest

from transmogrifai_tpu import plan as plan_mod
from transmogrifai_tpu.observability import metrics as om
from transmogrifai_tpu.observability import trace as ot
from transmogrifai_tpu.readers.readers import dataframe_to_table
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.workflow import OpWorkflow

pytestmark = pytest.mark.plan


# ---------------------------------------------------------------------------
# Synthetic example datasets (the reference CSVs are not shipped; the DAGs
# under test are the real example feature definitions)
# ---------------------------------------------------------------------------

def _titanic_df(n=240, seed=7):
    rng = np.random.RandomState(seed)
    sex = rng.choice(["male", "female"], n)
    pclass = rng.choice([1, 2, 3], n)
    age = np.where(rng.rand(n) < 0.15, np.nan, rng.uniform(1, 80, n))
    fare = np.round(rng.lognormal(2.5, 1.0, n), 2)
    survived = ((sex == "female").astype(float) * 0.6
                + (pclass == 1).astype(float) * 0.3
                + rng.rand(n) * 0.4 > 0.5).astype(float)
    return pd.DataFrame({
        "PassengerId": np.arange(1, n + 1),
        "Survived": survived,
        "Pclass": pclass,
        "Name": [f"Passenger, {'Mr.' if s == 'male' else 'Mrs.'} No{i}"
                 for i, s in enumerate(sex)],
        "Sex": sex,
        "Age": age,
        "SibSp": rng.randint(0, 4, n),
        "Parch": rng.randint(0, 3, n),
        "Ticket": [f"T{rng.randint(100, 999)}" for _ in range(n)],
        "Fare": fare,
        "Cabin": [None if rng.rand() < 0.7 else f"C{rng.randint(1, 99)}"
                  for _ in range(n)],
        "Embarked": rng.choice(["S", "C", "Q"], n),
    })


def _build_titanic(df, seed=42):
    from transmogrifai_tpu.examples.titanic import titanic_features
    from transmogrifai_tpu.impl.preparators import SanityChecker
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    survived, feature_vector = titanic_features()
    checked = survived.transform_with(SanityChecker(seed=seed),
                                      feature_vector)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=seed, models=[("OpLogisticRegression", None)])
        .set_input(survived, checked).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred, checked)), pred


def _iris_df(n=150, seed=5):
    rng = np.random.RandomState(seed)
    cls = rng.randint(0, 3, n)
    base = np.array([[5.0, 3.4, 1.5, 0.3],
                     [5.9, 2.8, 4.3, 1.3],
                     [6.6, 3.0, 5.6, 2.1]])
    X = base[cls] + rng.randn(n, 4) * 0.25
    names = np.array(["Iris-setosa", "Iris-versicolor", "Iris-virginica"])
    return pd.DataFrame({
        "sepalLength": X[:, 0], "sepalWidth": X[:, 1],
        "petalLength": X[:, 2], "petalWidth": X[:, 3],
        "irisClass": names[cls]})


def _build_iris(df, seed=42):
    from transmogrifai_tpu.examples.iris import iris_features
    from transmogrifai_tpu.impl.selector.factories import (
        MultiClassificationModelSelector)
    label, vec = iris_features()
    pred = (MultiClassificationModelSelector.with_cross_validation(
        seed=seed, models=[("OpLogisticRegression", None)])
        .set_input(label, vec).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred)), pred


def _boston_df(n=200, seed=11):
    rng = np.random.RandomState(seed)
    from transmogrifai_tpu.examples.boston import BOSTON_SCHEMA
    data = {}
    for c in BOSTON_SCHEMA[:-1]:
        if c == "chas":
            data[c] = (rng.rand(n) < 0.1).astype(float)
        else:
            data[c] = rng.uniform(0.1, 30.0, n)
    data["medv"] = (10 + 0.8 * data["rm"] - 0.3 * data["lstat"]
                    + rng.randn(n))
    return pd.DataFrame(data)


def _build_boston(df, seed=42):
    from transmogrifai_tpu.examples.boston import boston_features
    from transmogrifai_tpu.impl.selector.factories import (
        RegressionModelSelector)
    label, vec = boston_features()
    pred = (RegressionModelSelector.with_train_validation_split(
        seed=seed, models=[("OpLinearRegression", None)])
        .set_input(label, vec).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred)), pred


# ---------------------------------------------------------------------------
# Shared fitted models (train once per module; plan cache cleared right
# after so each test still enters with a clean LRU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def titanic():
    df = _titanic_df()
    wf, pred = _build_titanic(df)
    model = wf.train()
    plan_mod.clear_plan_cache()
    return model, df, pred


@pytest.fixture(scope="module")
def iris():
    df = _iris_df()
    wf, pred = _build_iris(df)
    model = wf.train()
    plan_mod.clear_plan_cache()
    return model, df, pred


@pytest.fixture(scope="module")
def boston():
    df = _boston_df()
    wf, pred = _build_boston(df)
    model = wf.train()
    plan_mod.clear_plan_cache()
    return model, df, pred


def _assert_tables_bit_equal(eager, planned):
    assert sorted(eager.column_names) == sorted(planned.column_names)
    for nm in eager.column_names:
        a = np.asarray(eager[nm].values)
        b = np.asarray(planned[nm].values)
        if a.dtype == object:
            assert all((x is None and y is None) or x == y
                       for x, y in zip(a, b)), f"column {nm} values differ"
        else:
            np.testing.assert_array_equal(
                a, b, err_msg=f"column {nm} values differ")
        ma, mb = eager[nm].mask, planned[nm].mask
        assert (ma is None) == (mb is None), f"column {nm} mask presence"
        if ma is not None:
            np.testing.assert_array_equal(
                np.asarray(ma), np.asarray(mb),
                err_msg=f"column {nm} masks differ")


def _score_both_ways(model, tbl):
    planned = model.score(table=tbl)
    assert plan_mod.cache_stats()["entries"] >= 1, \
        "score did not go through the planner"
    plan_mod.enable_planning(False)
    try:
        eager = model.score(table=tbl)
    finally:
        plan_mod.enable_planning(None)
    return eager, planned


# ---------------------------------------------------------------------------
# Bit-exact equivalence: planned vs eager, values AND masks
# ---------------------------------------------------------------------------

def test_titanic_planned_vs_eager_bit_exact(titanic):
    model, df, _ = titanic
    tbl = dataframe_to_table(df, model.raw_features)
    eager, planned = _score_both_ways(model, tbl)
    _assert_tables_bit_equal(eager, planned)


def test_iris_planned_vs_eager_bit_exact(iris):
    model, df, _ = iris
    tbl = dataframe_to_table(df, model.raw_features)
    eager, planned = _score_both_ways(model, tbl)
    _assert_tables_bit_equal(eager, planned)


def test_boston_planned_vs_eager_bit_exact(boston):
    model, df, _ = boston
    tbl = dataframe_to_table(df, model.raw_features)
    eager, planned = _score_both_ways(model, tbl)
    _assert_tables_bit_equal(eager, planned)


def test_train_under_planner_equals_eager_train():
    """The planned per-layer transformer runs feed estimator fits: a train
    with the planner on must produce the same fitted model — same winner,
    same kept slices, bit-identical scores — as an eager train."""
    df = _titanic_df(n=180, seed=3)
    wf_p, _ = _build_titanic(df, seed=4)
    model_p = wf_p.train()
    plan_mod.enable_planning(False)
    try:
        wf_e, _ = _build_titanic(df, seed=4)
        model_e = wf_e.train()
        # compare on the eager path for both models: only the TRAIN-path
        # difference is under test here
        tbl = dataframe_to_table(df, model_e.raw_features)
        scored_e = model_e.score(table=tbl)
        scored_p = model_p.score(table=tbl)
    finally:
        plan_mod.enable_planning(None)
    # separate workflows mint separate stage uids, so compare the result
    # features positionally (prediction, checked vector)
    for fe, fp in zip(model_e.result_features, model_p.result_features):
        np.testing.assert_array_equal(
            np.asarray(scored_e[fe.name].values),
            np.asarray(scored_p[fp.name].values),
            err_msg=f"result feature {fe.name} differs between planned "
            f"and eager trains")
    sc_e = next(s for s in model_e.stages
                if type(s).__name__ == "SanityCheckerModel")
    sc_p = next(s for s in model_p.stages
                if type(s).__name__ == "SanityCheckerModel")
    assert sc_e.keep_indices == sc_p.keep_indices


def test_micro_batch_scorer_bit_equal_and_plan_reuse(titanic):
    """micro_batch_score_function is a thin consumer of the planner: same
    records as row scoring, ONE cached plan reused across batch sizes."""
    from transmogrifai_tpu.local import micro_batch_score_function
    model, df, pred = titanic
    mb = micro_batch_score_function(model)
    rows = df.to_dict("records")
    out_a = mb(rows[:40])
    out_b = mb(rows[:17])    # different bucket → same plan, retraced only
    assert plan_mod.cache_stats()["entries"] == 1
    sf = model.score_function()
    for i in (0, 3, 16):
        row_score = sf(rows[i])[pred.name]
        assert out_a[i][pred.name]["prediction"] == pytest.approx(
            row_score["prediction"], abs=1e-5)
        assert out_b[i][pred.name]["prediction"] == out_a[i][pred.name][
            "prediction"]


# ---------------------------------------------------------------------------
# Dispatch accounting: the fusion win is measurable
# ---------------------------------------------------------------------------

def _dispatch_total():
    snap = om.registry().snapshot().get("tg_dispatch_total", {})
    return sum(snap.values())


def test_titanic_dispatch_count_planned_vs_eager(titanic):
    """The planned titanic transform run must launch ≥5× fewer top-level
    device executables than eager per-stage dispatch, and stay under a
    fixed small budget: the whole device tail collapses into two fused
    programs (vectorize→combine→sanity-slice, then the Prediction-emission
    barrier segment — docs/plan.md)."""
    model, df, _ = titanic
    tbl = dataframe_to_table(df, model.raw_features)
    om.enable_metrics(True)
    try:
        plan_mod.enable_planning(False)
        try:
            model.score(table=tbl)
        finally:
            plan_mod.enable_planning(None)
        eager_n = _dispatch_total()
        om.reset()
        om.enable_metrics(True)
        model.score(table=tbl)
        planned_n = _dispatch_total()
    finally:
        om.enable_metrics(None)
    assert eager_n >= 10, (
        f"eager titanic transform should lower-bound ≥10 launches, "
        f"saw {eager_n}")
    assert planned_n <= 3, f"planned run dispatched {planned_n} programs"
    assert eager_n >= 5 * planned_n, (eager_n, planned_n)


def test_dispatch_counter_zero_writes_when_metrics_off(titanic):
    model, df, _ = titanic
    assert not om.metrics_enabled()
    model.score(table=dataframe_to_table(df, model.raw_features))
    assert om.registry().snapshot() == {}


def test_plan_spans_emitted_and_compile_cached(titanic):
    model, df, _ = titanic
    tbl = dataframe_to_table(df, model.raw_features)
    ot.enable_tracing(True)
    try:
        model.score(table=tbl)
        model.score(table=tbl)
    finally:
        ot.enable_tracing(None)
    names = [s.name for s in ot.tracer().finished()]
    assert names.count("plan.compile") == 1, "plan was not cached"
    assert names.count("plan.execute") == 2
    assert "plan.segment" in names


# ---------------------------------------------------------------------------
# Packed device uploads
# ---------------------------------------------------------------------------

def test_to_device_packs_transfers():
    import jax
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import OPVector, Real, Text
    rng = np.random.RandomState(0)
    n = 64
    cols = {}
    for i in range(10):
        mask = rng.rand(n) < 0.9
        cols[f"r{i}"] = Column(Real, rng.randn(n).astype(np.float32),
                               mask if i % 2 == 0 else None)
    cols["vec"] = Column(OPVector, rng.randn(n, 5).astype(np.float32), None)
    txt = np.empty(n, dtype=object)
    txt[:] = "hello"
    cols["t"] = Column(Text, txt, None)
    tbl = FeatureTable(cols, n)
    om.enable_metrics(True)
    try:
        dev = tbl.to_device()
    finally:
        om.enable_metrics(None)
    snap = om.registry().snapshot()["tg_device_transfer_total"]
    transfers = sum(snap.values())
    # 11 device-kind columns land in ≤2 uploads (one f32 block + one mask
    # block) — O(dtypes), not O(columns)
    assert transfers <= 2, f"{transfers} transfers for 11 device columns"
    om.reset()
    for name, col in cols.items():
        got = dev[name]
        if name == "t":
            assert got.values.dtype == object
            continue
        assert isinstance(got.values, jax.Array), name
        np.testing.assert_array_equal(np.asarray(got.values), col.values)
        assert (got.mask is None) == (col.mask is None)
        if col.mask is not None:
            np.testing.assert_array_equal(np.asarray(got.mask), col.mask)


# ---------------------------------------------------------------------------
# Cache bounds + eligibility gating
# ---------------------------------------------------------------------------

def test_plan_cache_lru_bounded(titanic, monkeypatch):
    model, df, _ = titanic
    monkeypatch.setattr(plan_mod, "_PLAN_CACHE_MAX", 2)
    stages = list(model.stages)
    for k in (10, 20, 30, 40):   # distinct schemas → distinct plan keys
        tbl = dataframe_to_table(df.iloc[:, :], model.raw_features)
        # vary the fingerprint by dropping an unused-for-fusion column is
        # fiddly; instead vary keep/extra options which key the cache too
        plan_mod.get_plan(stages, tbl, keep_intermediates=False,
                          extra_keep=(f"x{k}",), cat="score")
    assert len(plan_mod._PLAN_CACHE) <= 2


def test_chaos_disables_planning_for_non_plan_sites():
    with faults.injected({"dag.stage_fit": {"mode": "raise"}}):
        assert not plan_mod.planning_applicable()
    with faults.injected({"plan.segment_execute": {"mode": "raise"}}):
        assert plan_mod.planning_applicable()
    plan_mod.enable_planning(False)
    try:
        assert not plan_mod.planning_applicable()
    finally:
        plan_mod.enable_planning(None)
    assert plan_mod.planning_applicable()


def test_keeps_planner_is_the_old_prefix_rule():
    """The registry's ``keeps_planner`` field replaced a tuple of site-name
    prefixes in ``planning_applicable``: over every registered site the two
    agree, and an armed name the registry does not hold runs eager."""
    prefixes = ("plan.", "serve.", "drift.", "oom.", "fleet.", "aot.",
                "place.")
    for name, spec in faults.ALL_SITES.items():
        assert spec.keeps_planner == name.startswith(prefixes), name
        with faults.injected({name: {"mode": spec.modes[0]}}):
            assert plan_mod.planning_applicable() == spec.keeps_planner, name
    with faults.injected({"serve.unregistered": {"mode": "raise"}}):
        assert not plan_mod.planning_applicable()


def test_chaos_env_disables_planning(monkeypatch):
    monkeypatch.setenv(faults.CHAOS_ENV, "1")
    assert not plan_mod.planning_applicable()


@pytest.mark.chaos
def test_mid_segment_fault_falls_back_to_eager(titanic):
    """A fault raised inside a planned segment degrades that run to eager
    per-stage dispatch: identical results, a recorded plan_fallback
    FaultLog entry, and a tg_faults_total counter tick."""
    from transmogrifai_tpu.robustness.policy import FaultLog
    model, df, _ = titanic
    tbl = dataframe_to_table(df, model.raw_features)
    plan_mod.enable_planning(False)
    try:
        expected = model.score(table=tbl)
    finally:
        plan_mod.enable_planning(None)
    log = FaultLog()
    om.enable_metrics(True)
    try:
        with faults.injected({"plan.segment_execute": {
                "mode": "raise", "transient": True, "nth": 1, "count": 1}}):
            with log.activate():
                out = model.score(table=tbl)
        fallbacks = log.of_kind("plan_fallback")
        assert fallbacks, "fallback was not recorded in the FaultLog"
        assert "TransientFaultError" in fallbacks[0].detail["error"]
        snap = om.registry().snapshot()
        assert snap["tg_faults_total"].get("kind=plan_fallback") == 1.0
    finally:
        om.enable_metrics(None)
    _assert_tables_bit_equal(expected, out)
    assert log.to_json()["planFallbacks"]


def test_plan_build_failure_is_recorded_and_runs_eager(titanic, monkeypatch):
    """A plan whose BUILD raises degrades to eager dispatch like a run that
    raises — and is just as visible: a ``plan_fallback`` report from site
    ``plan.compile`` (it used to be a log line only, so a chip run whose
    plans never built kept a clean ``summary()["faults"]``)."""
    from transmogrifai_tpu.robustness.policy import FaultLog
    model, df, _ = titanic
    tbl = dataframe_to_table(df, model.raw_features)
    plan_mod.enable_planning(False)
    try:
        expected = model.score(table=tbl)
    finally:
        plan_mod.enable_planning(None)

    def boom(*a, **k):
        raise RuntimeError("Mosaic refused the segment")

    monkeypatch.setattr(plan_mod, "_build_plan", boom)
    log = FaultLog()
    with log.activate():
        out = model.score(table=tbl)
    _assert_tables_bit_equal(expected, out)
    fallbacks = log.to_json()["planFallbacks"]
    assert fallbacks and all(r["site"] == "plan.compile" for r in fallbacks)
    assert "Mosaic refused" in fallbacks[0]["detail"]["error"]
    # the infeasible sequence is cached as such: reported once, not per run
    with log.activate():
        model.score(table=tbl)
    assert len(log.to_json()["planFallbacks"]) == len(fallbacks)


# ---------------------------------------------------------------------------
# Vectorized value-lambda host fallback (stages/base satellite)
# ---------------------------------------------------------------------------

def _mk_real_table(n=50, missing=False, seed=0):
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import Real
    rng = np.random.RandomState(seed)
    vals = rng.randn(n).astype(np.float32)
    mask = (rng.rand(n) < 0.8) if missing else None
    raw = [None if (mask is not None and not mask[i]) else float(vals[i])
           for i in range(n)]
    return FeatureTable({"a": Column.of_values(Real, raw),
                         "b": Column.of_values(Real, list(range(n)))}, n), raw


def _wire_binary(fn, output_type=None):
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.stages.base import BinaryTransformer
    from transmogrifai_tpu.types import Real
    fa = FeatureBuilder.Real("a").extract_field().as_predictor()
    fb = FeatureBuilder.Real("b").extract_field().as_predictor()
    return BinaryTransformer("vt", fn, output_type or Real).set_input(fa, fb)


def test_value_lambda_vectorizes_ufunc_numeric():
    """Numeric inputs + ufunc-compatible fn → one numpy sweep, bit-equal to
    the per-cell row map (including NaN-result → missing semantics)."""
    from transmogrifai_tpu.stages.base import (
        _iter_cell_values, _vectorized_value_transform)
    from transmogrifai_tpu.table import Column
    tbl, _ = _mk_real_table()
    stage = _wire_binary(lambda a, b: a * 2.0 + np.log(b))  # log(0) → -inf ok
    cols = [tbl["a"], tbl["b"]]
    fast = _vectorized_value_transform(stage.transform_fn, stage.output_type,
                                       cols)
    assert fast is not None, "numeric ufunc lambda should vectorize"
    slow = Column.of_values(stage.output_type,
                            [stage.transform_fn(*args)
                             for args in _iter_cell_values(cols)])
    np.testing.assert_array_equal(np.asarray(fast.values),
                                  np.asarray(slow.values))
    np.testing.assert_array_equal(np.asarray(fast.mask),
                                  np.asarray(slow.mask))
    # via the public path too
    out = stage.transform_column(tbl)
    np.testing.assert_array_equal(np.asarray(out.values),
                                  np.asarray(slow.values))


def test_value_lambda_nan_result_is_missing():
    tbl, _ = _mk_real_table()
    stage = _wire_binary(lambda a, b: np.sqrt(a))   # negative → NaN
    out = stage.transform_column(tbl)
    neg = np.asarray(tbl["a"].values) < 0
    assert neg.any()
    assert not np.asarray(out.mask)[neg].any()
    assert np.asarray(out.values)[neg].sum() == 0.0


def test_value_lambda_masked_inputs_keep_row_map():
    """None handling must stay exact: masked inputs take the row-map path
    where the lambda sees python None."""
    tbl, raw = _mk_real_table(missing=True)
    seen = []
    stage = _wire_binary(
        lambda a, b: seen.append(a) or ((a or 0.0) + (b or 0.0)))
    stage.transform_column(tbl)
    assert None in seen, "masked input should reach the lambda as None"


def test_value_lambda_branching_fn_falls_back():
    tbl, _ = _mk_real_table()
    stage = _wire_binary(lambda a, b: a if a > b else b)  # raises on arrays
    out = stage.transform_column(tbl)
    expect = [max(x, y) for x, y in zip(np.asarray(tbl["a"].values).tolist(),
                                        np.asarray(tbl["b"].values).tolist())]
    np.testing.assert_allclose(np.asarray(out.values),
                               np.asarray(expect, dtype=np.float32))
