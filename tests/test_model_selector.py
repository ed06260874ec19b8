"""ModelSelector + validators + splitters tests (model: reference
ModelSelectorTest, OpCrossValidationTest, DataBalancerTest, DataCutterTest)."""
import numpy as np
import pytest

from transmogrifai_tpu import FeatureBuilder, FeatureTable, Column
from transmogrifai_tpu.types import OPVector, RealNN, Prediction
from transmogrifai_tpu.impl.selector import (
    BinaryClassificationModelSelector, MultiClassificationModelSelector,
    RegressionModelSelector)
from transmogrifai_tpu.impl.tuning import (
    DataBalancer, DataCutter, DataSplitter, OpCrossValidation,
    OpTrainValidationSplit)
from transmogrifai_tpu.evaluators.base import prediction_parts


def _binary_table(n=300, d=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d).astype(np.float32)
    y = ((X @ w + 0.2 * rng.randn(n)) > 0).astype(np.float32)
    return FeatureTable({
        "label": Column(RealNN, y, None),
        "features": Column(OPVector, X, None)}, n), y


def _wire(sel):
    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    feats = FeatureBuilder.OPVector("features").extract_field().as_predictor()
    sel.set_input(label, feats)
    return sel


@pytest.mark.parametrize("factory,per_fold,fits", [
    (BinaryClassificationModelSelector, (6, 18, 18, 3), 135),
    (MultiClassificationModelSelector, (6, 18), 72)])
def test_stock_selector_fit_count(factory, per_fold, fits):
    """The stock selectors' sweep sizes, which the benchmark cells' ``fits``
    check and PERF.md section 2 rest on: folds x the families' default
    grids (binary LR + RF + GBT + SVC, multiclass LR + RF). Counted from
    ``default_grid``, so TG_FAST_GRIDS does not enter."""
    sel = factory.with_cross_validation()
    grids = tuple(len(fam.default_grid(sel.problem)) for fam, _ in sel.models)
    assert grids == per_fold
    assert sel.validator.num_folds * sum(grids) == fits


def test_binary_selector_cv(monkeypatch):
    # this test pins the FULL reference default grids (6-point LR grid), so
    # opt out of the suite-wide TG_FAST_GRIDS shrink
    monkeypatch.setenv("TG_FAST_GRIDS", "0")
    tbl, y = _binary_table()
    sel = _wire(BinaryClassificationModelSelector.with_cross_validation(seed=7))
    model = sel.fit(tbl)
    s = model.summary
    assert s.best_model_type in (
        "OpLogisticRegression", "OpRandomForestClassifier",
        "OpGBTClassifier", "OpLinearSVC")
    assert s.best_metric_value > 0.8   # separable data → high AuPR
    assert len(s.validation_results) == 4  # reference default model types
    # each family evaluated over folds × grid
    lr = next(r for r in s.validation_results if r.family == "OpLogisticRegression")
    assert lr.fold_metrics.shape == (3, 6)
    # scoring produces a Prediction column
    out = model.transform_column(tbl)
    parts = prediction_parts(out)
    assert set(parts) >= {"prediction"}
    acc = (parts["prediction"] == y).mean()
    assert acc > 0.85
    # holdout metrics recorded
    assert "AuROC" in s.holdout_evaluation
    assert model.summary_pretty().startswith("-- ModelSelector")


def test_selector_row_dual_matches_columnar():
    tbl, _ = _binary_table(n=100)
    model = _wire(BinaryClassificationModelSelector.with_cross_validation()).fit(tbl)
    col = model.transform_column(tbl)
    keys = col.metadata["keys"]
    row_out = model.transform_row(
        {"features": np.asarray(tbl["features"].values)[0].tolist()})
    col_row0 = {k: float(v) for k, v in zip(keys, np.asarray(col.values)[0])}
    for k in keys:
        assert np.isclose(row_out[k], col_row0[k], atol=1e-5), k


def test_multiclass_selector():
    rng = np.random.RandomState(3)
    n = 300
    X = rng.randn(n, 3).astype(np.float32)
    y = np.argmax(X[:, :3] + 0.3 * rng.randn(n, 3), axis=1).astype(np.float32)
    tbl = FeatureTable({
        "label": Column(RealNN, y, None),
        "features": Column(OPVector, X, None)}, n)
    sel = _wire(MultiClassificationModelSelector.with_cross_validation())
    model = sel.fit(tbl)
    parts = prediction_parts(model.transform_column(tbl))
    acc = (parts["prediction"] == y).mean()
    assert acc > 0.8
    assert parts["probability"].shape == (n, 3)


def test_regression_selector():
    rng = np.random.RandomState(4)
    n = 300
    X = rng.randn(n, 3).astype(np.float32)
    y = (X @ np.array([1.0, -2.0, 0.5]) + 3.0 + 0.1 * rng.randn(n)).astype(np.float32)
    tbl = FeatureTable({
        "label": Column(RealNN, y, None),
        "features": Column(OPVector, X, None)}, n)
    sel = _wire(RegressionModelSelector.with_cross_validation())
    model = sel.fit(tbl)
    parts = prediction_parts(model.transform_column(tbl))
    rmse = np.sqrt(((parts["prediction"] - y) ** 2).mean())
    assert rmse < 0.3
    assert model.summary.best_model_type == "OpLinearRegression"


def test_train_validation_split_selector():
    tbl, _ = _binary_table()
    sel = _wire(BinaryClassificationModelSelector.with_train_validation_split(seed=1))
    model = sel.fit(tbl)
    lr = next(r for r in model.summary.validation_results
              if r.family == "OpLogisticRegression")
    assert lr.fold_metrics.shape[0] == 1   # single split


def test_data_balancer():
    rng = np.random.RandomState(5)
    y = (rng.rand(10_000) < 0.02).astype(np.float32)  # 2% positives
    b = DataBalancer(sample_fraction=0.1, seed=0)
    prep = b.pre_validation_prepare(y)
    yb = y[prep.indices]
    frac = yb.mean()
    assert 0.08 < frac < 0.12
    assert prep.summary["balanced"]
    # already balanced data untouched
    y2 = (rng.rand(1000) < 0.4).astype(np.float32)
    prep2 = DataBalancer(sample_fraction=0.1).pre_validation_prepare(y2)
    assert len(prep2.indices) == 1000


def test_data_cutter():
    rng = np.random.RandomState(6)
    y = rng.choice([0, 1, 2, 3, 4], p=[0.4, 0.3, 0.2, 0.06, 0.04], size=5000)
    c = DataCutter(max_label_categories=3, seed=0)
    prep = c.pre_validation_prepare(y.astype(np.float32))
    assert prep.summary["labelsKept"] == [0, 1, 2]
    assert prep.label_mapping == {0: 0, 1: 1, 2: 2}
    kept = y[prep.indices]
    assert set(kept) == {0, 1, 2}
    with pytest.raises(ValueError):
        DataCutter(min_label_fraction=0.6)


def test_kfold_masks_partition():
    cv = OpCrossValidation(num_folds=4, seed=0)
    y = np.arange(103, dtype=np.float32) % 2
    masks = cv.make_splits(y)
    assert masks.shape == (4, 103)
    assert masks.sum(axis=0).tolist() == [1] * 103   # each row in exactly one fold
    strat = OpCrossValidation(num_folds=4, seed=0, stratify=True)
    smasks = strat.make_splits(y)
    assert smasks.sum(axis=0).tolist() == [1] * 103
    # stratified: each fold has both classes
    for f in range(4):
        assert len(np.unique(y[smasks[f]])) == 2


def test_fold_sliced_scoring_matches_masked_path():
    """The fold-sliced scoring path (gather each fold's validation rows)
    must produce the same per-fold metrics as full-row masked scoring (the
    mesh / explicit-mask path)."""
    import numpy as np
    import jax.numpy as jnp
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    import transmogrifai_tpu.models.linear  # noqa: F401
    import transmogrifai_tpu.models.trees   # noqa: F401

    rng = np.random.RandomState(0)
    n, d = 600, 8
    X = jnp.asarray(rng.randn(n, d).astype(np.float32))
    y = jnp.asarray((np.asarray(X) @ rng.randn(d).astype(np.float32)
                     + 0.3 * rng.randn(n) > 0).astype(np.float32))
    models = [(MODEL_REGISTRY["OpLogisticRegression"],
               [{"regParam": 0.01, "elasticNetParam": 0.0},
                {"regParam": 0.1, "elasticNetParam": 0.5}]),
              (MODEL_REGISTRY["OpDecisionTreeClassifier"],
               [{"maxDepth": 3}])]
    cv = OpCrossValidation(num_folds=3, seed=7)

    sliced = cv.validate(models, X, y, "binary", "AuPR", True, 2)
    # fold_sliced=False forces the full-row masked scoring path (the same
    # code the mesh path runs) on identical seeded splits
    masked = cv.validate(models, X, y, "binary", "AuPR", True, 2,
                         fold_sliced=False)
    for i in range(len(models)):
        got = np.asarray(sliced.results[i].fold_metrics)          # (3, G)
        want = np.asarray(masked.results[i].fold_metrics)
        assert np.allclose(got, want, rtol=1e-4, atol=1e-5), (got, want)


def test_fold_sliced_pins_binned_metric_choice():
    """Fold-slicing shrinks the metric's row axis; the binned-vs-exact
    AuROC choice must follow the PRE-slice row count so both scoring paths
    agree even when n is above the binned threshold but n/F is below it.
    Both counts are row BUCKETS (900 rows pad to 1024, a fold's 300 to 512),
    and the validator must read the threshold when it is called, as the
    metrics do: pinned, the two paths agree to the bit; unpinned, or pinned
    to a threshold captured at import, the boosted scores differ by 4e-5."""
    import numpy as np
    import jax.numpy as jnp
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    from transmogrifai_tpu.ops import metrics as M
    import transmogrifai_tpu.models.trees  # noqa: F401

    old = M._BINNED_MIN_N
    M._BINNED_MIN_N = 600          # the table's 1024 above, a fold's 512 below
    # _BINNED_MIN_N is read at trace time inside the module-level-jitted
    # metrics; stale per-shape traces from earlier tests would silently
    # bypass the patched threshold (and the un-patch below)
    M.auroc_masked.clear_cache()
    M.aupr_masked.clear_cache()
    try:
        rng = np.random.RandomState(1)
        n, d = 900, 6
        X = jnp.asarray(rng.randn(n, d).astype(np.float32))
        y = jnp.asarray((np.asarray(X) @ rng.randn(d).astype(np.float32)
                         + 0.5 * rng.randn(n) > 0).astype(np.float32))
        # a tree family: linear families opt out of fold-sliced predicts
        # (fold_sliced_predict=False), so only trees exercise the pin; a
        # boosted one: one shallow tree's few distinct scores are the same
        # tie groups binned or not
        models = [(MODEL_REGISTRY["OpGBTClassifier"],
                   [{"maxDepth": 3, "maxIter": 8}])]
        cv = OpCrossValidation(num_folds=3, seed=3)
        sliced = cv.validate(models, X, y, "binary", "AuROC", True, 2)
        masked = cv.validate(models, X, y, "binary", "AuROC", True, 2,
                             fold_sliced=False)
        got = np.asarray(sliced.results[0].fold_metrics)
        want = np.asarray(masked.results[0].fold_metrics)
        # same algorithm (binned) on both paths -> the same values
        assert np.allclose(got, want, rtol=0, atol=1e-6), (got, want)
    finally:
        M._BINNED_MIN_N = old
        M.auroc_masked.clear_cache()
        M.aupr_masked.clear_cache()
