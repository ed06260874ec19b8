"""Round-3 ADVICE fixes (round 2's review): deindexer rounding,
forest n_bins guard, TimePeriodListTransformer width locking, persistence
dangling stage-ref warning, max_eval_rows surfaced in the selector summary."""
import warnings

import numpy as np
import pytest


def test_deindexer_rounds_float_noise():
    """int(round(v)): 1.9999999 decodes to labels[2], -0.3 stays in-range 0,
    -0.6 is out-of-range (ADVICE round 2 #3)."""
    from transmogrifai_tpu.impl.preparators.prediction_deindexer import (
        PredictionDeIndexerModel)
    m = PredictionDeIndexerModel(labels=["a", "b", "c"])
    assert m._decode(1.9999999) == "c"
    assert m._decode(-0.3) == "a"
    assert m._decode(-0.6) == m.unseen_name
    assert m._decode(2.4) == "c"
    assert m._decode(2.6) == m.unseen_name


def test_forest_n_bins_guard():
    """bf16 routing is exact only for codes <= 256; larger n_bins raises."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops.forest import forest_leaf_sums, forest_predict
    codes = jnp.zeros((4, 2), jnp.int32)
    fh = jnp.zeros((1, 1), jnp.int32)
    bh = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="n_bins"):
        forest_leaf_sums(codes, fh, bh, jnp.ones((4, 1)), depth=1, n_bins=512)
    with pytest.raises(ValueError, match="n_bins"):
        forest_predict(codes, fh, bh, jnp.ones((1, 2, 1)), depth=1,
                       n_bins=512)


def test_time_period_list_width_locks_on_first_batch():
    """width=None locks to the first (train) batch's longest list so later
    batches emit the same column width (ADVICE round 2 #4)."""
    from transmogrifai_tpu.impl.feature.dates import TimePeriodListTransformer
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import DateList
    from transmogrifai_tpu.features import FeatureBuilder

    f = FeatureBuilder.DateList("d").extract_field().as_predictor()
    t = TimePeriodListTransformer(period="DayOfWeek").set_input(f)
    day = 86400000
    train = FeatureTable(
        {"d": Column.of_values(DateList, [[day, 2 * day, 3 * day], [day]])}, 2)
    score = FeatureTable({"d": Column.of_values(DateList, [[day]])}, 1)
    out_train = t.transform_column(train)
    out_score = t.transform_column(score)
    assert np.asarray(out_train.values).shape[1] == 3
    assert np.asarray(out_score.values).shape[1] == 3  # not 1


def test_save_model_warns_on_dangling_stage_ref(tmp_path):
    """A stage attribute referencing a stage outside the saved plan warns at
    save time instead of failing at load (ADVICE round 2 #5)."""
    from transmogrifai_tpu import FeatureBuilder, FeatureTable, Column
    from transmogrifai_tpu.types import Real
    from transmogrifai_tpu.workflow import OpWorkflow
    from transmogrifai_tpu.persistence import save_model
    from transmogrifai_tpu.impl.feature.math import ScalarOp

    a = FeatureBuilder.Real("a").extract_field().as_predictor()
    out = a + 1.0
    tbl = FeatureTable({"a": Column.of_values(Real, [1.0, 2.0])}, 2)
    model = (OpWorkflow().set_input_table(tbl)
             .set_result_features(out).train())
    # sneak an out-of-plan stage reference onto a saved stage
    stray = ScalarOp("+", 7.0)
    model.stages[0]._stray = stray
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        save_model(model, str(tmp_path / "m"))
    msgs = [str(x.message) for x in w]
    assert any(stray.uid in m for m in msgs), msgs


def test_selector_summary_surfaces_eval_row_cap():
    """max_eval_rows lands in the summary JSON (ADVICE round 2 #1)."""
    from transmogrifai_tpu.impl.selector.model_selector import (
        ModelSelectorSummary)
    s = ModelSelectorSummary(
        validation_type="OpCrossValidation", validation_metric="AuPR",
        problem="binary", best_model_type="OpLogisticRegression",
        best_hyper={}, best_metric_value=0.9,
        validation_eval_row_cap=131072)
    assert s.to_json()["validationEvalRowCap"] == 131072


def test_linear_fit_survives_fold_degenerate_columns():
    """A column constant within a config's weighted rows (rare one-hot slot
    whose nonzero rows all fall in the val fold) must not NaN the batched
    solvers — dead columns get coefficient 0 (round-3 fix; previously every
    CV sweep on Titanic returned constant LR/SVC scores)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models.linear import (_fit_logreg_batch,
                                                 _fit_svc_batch)
    rng = np.random.RandomState(0)
    n, d = 512, 8
    X = rng.randn(n, d).astype(np.float32)
    X[:, 3] = 0.0
    X[:4, 3] = 1.0          # nonzero only in rows 0-3
    y = (X[:, 0] > 0).astype(np.float32)
    W = np.ones((2, n), np.float32)
    W[:, :4] = 0.0          # ...which carry zero weight for every config
    Xd, yd, Wd = jnp.asarray(X), jnp.asarray(y), jnp.asarray(W)
    reg = jnp.asarray([0.01, 0.1], jnp.float32)
    en = jnp.zeros(2, jnp.float32)
    for sweep in (False, True):
        coef, bias = _fit_logreg_batch(Xd, yd, Wd, reg, en, sweep=sweep)
        assert bool(jnp.isfinite(coef).all()) and bool(jnp.isfinite(bias).all())
        assert abs(float(coef[0, 3])) < 1e-6      # dead column: coef 0
        assert float(jnp.abs(coef[0]).max()) > 0.1  # live columns learned
        coef, bias = _fit_svc_batch(Xd, yd, Wd, reg, sweep=sweep)
        assert bool(jnp.isfinite(coef).all()) and bool(jnp.isfinite(bias).all())
        assert abs(float(coef[0, 3])) < 1e-6


def test_loco_device_side_bounded_variants():
    """LOCO builds zeroed variants on device in bounded blocks — peak
    variant bytes stay under the configured budget and results match the
    unchunked math (VERDICT r2 #7)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.insights.record_insights import RecordInsightsLOCO
    from transmogrifai_tpu.models.api import MODEL_REGISTRY, FittedParams
    import transmogrifai_tpu.models.linear  # noqa: F401
    from transmogrifai_tpu.impl.selector.model_selector import (
        ModelSelectorSummary, SelectedModel)
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import OPVector
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.vector_metadata import (VectorColumnMetadata,
                                                   VectorMetadata)

    rng = np.random.RandomState(0)
    n, d = 64, 6
    X = rng.randn(n, d).astype(np.float32)
    coef = rng.randn(d).astype(np.float32)
    fitted = FittedParams(family="OpLogisticRegression",
                          params={"coef": coef, "bias": np.float32(0.1)},
                          hyper={}, num_classes=2)
    summary = ModelSelectorSummary(
        validation_type="cv", validation_metric="AuPR", problem="binary",
        best_model_type="OpLogisticRegression", best_hyper={},
        best_metric_value=0.9)
    sel = SelectedModel(fitted=fitted, summary=summary)
    vm = VectorMetadata.of("v", [
        VectorColumnMetadata(f"f{i}", "Real", f"f{i}", None)
        for i in range(d)])
    f = FeatureBuilder.OPVector("v").extract_field().as_predictor()
    tbl = FeatureTable({"v": Column(OPVector, X, None,
                                    {"vector_meta": vm})}, n)

    loco = RecordInsightsLOCO(sel, top_k=3).set_input(f)
    # force tiny blocks so chunking is exercised
    loco.VARIANT_BLOCK_BYTES = 4 * 8 * d   # 8 variant rows at a time
    out_chunked = loco.transform_column(tbl)
    assert loco._peak_variant_bytes <= 4 * 8 * d

    loco2 = RecordInsightsLOCO(sel, top_k=3).set_input(f)
    out_full = loco2.transform_column(tbl)
    assert loco2._peak_variant_bytes <= loco2.VARIANT_BLOCK_BYTES
    for a, b in zip(out_chunked.values, out_full.values):
        assert a == b


def _titanic_like_model():
    import pandas as pd
    import transmogrifai_tpu as tg
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.preparators import SanityChecker
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.workflow import OpWorkflow

    rng = np.random.RandomState(9)
    n = 260
    x1, x2 = rng.randn(n), rng.randn(n)
    x3 = np.where(rng.rand(n) < 0.2, np.nan, rng.randn(n))
    df = pd.DataFrame({"x1": x1, "x2": x2, "x3": x3,
                       "c": rng.choice(["a", "b", "c"], n),
                       "y": (x1 + 0.5 * x2 > 0).astype(float)})
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real("x1").extract_field().as_predictor(),
             FeatureBuilder.Real("x2").extract_field().as_predictor(),
             FeatureBuilder.Real("x3").extract_field().as_predictor(),
             FeatureBuilder.PickList("c").extract_field().as_predictor()]
    checked = label.transform_with(SanityChecker(seed=3),
                                   tg.transmogrify(feats))
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=3, models=[("OpLogisticRegression", None)])
        .set_input(label, checked).get_output())
    model = (OpWorkflow().set_input_dataset(df)
             .set_result_features(pred, checked).train())
    return model, df, pred


def test_compiled_score_matches_plain():
    """The fused one-program serve path produces the same scores as the
    stage-by-stage path, across different micro-batch sizes that share the
    padding bucket (VERDICT r2 #6)."""
    from transmogrifai_tpu.local.scoring import compiled_score_function
    model, df, pred = _titanic_like_model()
    compiled = compiled_score_function(model)
    for sl in (slice(0, 260), slice(0, 100), slice(40, 97)):
        part = df.iloc[sl]
        from transmogrifai_tpu.readers.readers import dataframe_to_table
        tbl = dataframe_to_table(part, model.raw_features)
        plain = model.score(table=tbl)
        fused = compiled(tbl)
        np.testing.assert_allclose(
            np.asarray(fused[pred.name].values, np.float32),
            np.asarray(plain[pred.name].values, np.float32), atol=1e-5)
        # the checked vector column (a fused output) also matches
        chk = [c for c in plain.column_names if "sanityCheck" in c][0]
        np.testing.assert_allclose(
            np.asarray(fused[chk].values, np.float32),
            np.asarray(plain[chk].values, np.float32), atol=1e-5)


def test_micro_batch_scorer_uses_compiled_path():
    from transmogrifai_tpu.local.scoring import micro_batch_score_function
    model, df, pred = _titanic_like_model()
    fn = micro_batch_score_function(model)
    rows = df.to_dict("records")[:9]
    out = fn(rows)
    assert len(out) == 9
    assert all("prediction" in r[pred.name] for r in out)


def test_sweep_fidelity_ranking_agreement():
    """Sampled sweep (default max_eval_rows + sweep_fit_batch) ranks configs
    consistently with the exact sweep (max_eval_rows=None +
    exact_sweep_fits): what the sweep's sampling constants (32768
    evaluation rows, 8192 split-search rows, 16 trees, 12 rounds) rest on."""
    import jax.numpy as jnp
    from scipy import stats as sps
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    import transmogrifai_tpu.models.linear, transmogrifai_tpu.models.trees  # noqa

    rng = np.random.RandomState(0)
    n, d = 20_000, 16
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d).astype(np.float32)
         + rng.randn(n) > 0).astype(np.float32)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    models = [
        (MODEL_REGISTRY["OpLogisticRegression"],
         [{"regParam": r, "elasticNetParam": e}
          for r in (0.001, 0.01, 0.1) for e in (0.0, 0.5)]),
        (MODEL_REGISTRY["OpRandomForestClassifier"],
         [{"maxDepth": dd, "minInstancesPerNode": 10, "minInfoGain": mg,
           "numTrees": 20, "subsamplingRate": 1.0}
          for dd in (3, 5) for mg in (0.001, 0.1)]),
    ]

    def run(exact):
        cv = OpCrossValidation(num_folds=3, seed=0,
                               max_eval_rows=None if exact else 4096,
                               exact_sweep_fits=exact)
        best = cv.validate(models, Xd, yd, "binary", "AuROC", True, 2)
        return best, {r.family: np.asarray(r.mean_metrics)
                      for r in best.results}

    b_def, r_def = run(False)
    b_ex, r_ex = run(True)
    assert b_def.family_name == b_ex.family_name
    all_d = np.concatenate([r_def[f] for f in r_def])
    all_e = np.concatenate([r_ex[f] for f in r_def])
    rho = sps.spearmanr(all_d, all_e).statistic
    assert rho > 0.85, rho
    # the sampled winner is within noise of the exact winner's metric
    assert abs(b_def.metric_value - b_ex.metric_value) < 0.02


def test_factories_forward_validator_kwargs():
    """Every selector factory forwards validator kwargs so the exact sweep
    is reachable without hand-building validators."""
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector, MultiClassificationModelSelector,
        RegressionModelSelector)
    for fac in (BinaryClassificationModelSelector,
                MultiClassificationModelSelector, RegressionModelSelector):
        for ctor in (fac.with_cross_validation,
                     fac.with_train_validation_split):
            sel = ctor(max_eval_rows=None, exact_sweep_fits=True)
            assert sel.validator.max_eval_rows is None
            assert sel.validator.exact_sweep_fits is True
