"""Regression tests for the round-4 advisor/review fixes: scale-exact
dead-column detection, GBT sweep leaf noise clamp, date-list width locking,
fused-path mask propagation, and the public distributed-init probe."""
import inspect

import numpy as np
import jax.numpy as jnp
import pytest

from transmogrifai_tpu.models.linear import (
    _BatchStd, _lane_moments, _moment_std, global_affine)


def _lane_scales(X, w):
    """Per-lane scales of the moment-based linear regression (PR 30: the
    per-lane ``_standardize`` copy of the table is gone; a lane standardises
    by algebra on its moments of the globally mapped columns)."""
    X = jnp.asarray(X)
    g_mean, g_scale = global_affine(X)
    M = _lane_moments(X, jnp.zeros(X.shape[0]), jnp.asarray(w)[None, :],
                      g_mean, g_scale, 0.0)
    return np.asarray(_moment_std(M)[2][0]) * np.asarray(g_scale)


def test_standardize_keeps_tiny_scale_and_huge_offset_columns():
    """ADVICE r3: a genuinely informative column with natural scale 1e-4
    (var 1e-8) or a huge-offset epoch-millis column (var/ex2 ~ 1e-10) must
    NOT be treated as constant; an exactly-constant column must."""
    rng = np.random.default_rng(0)
    n = 256
    tiny = (rng.standard_normal(n) * 1e-4).astype(np.float32)
    epoch = (1.7e12 + rng.standard_normal(n) * 2.5e7).astype(np.float32)
    const = np.full(n, 3.25, np.float32)
    scale = _lane_scales(np.stack([tiny, epoch, const], 1),
                         np.ones(n, np.float32))
    assert scale[0] < 1e3          # tiny-scale column alive
    assert scale[1] < 1e9          # epoch column alive
    assert scale[2] >= 1e23        # constant column dead


def test_standardize_dead_test_respects_weights():
    # column varies globally but is constant within the weighted rows
    scale = _lane_scales(np.array([[1.0], [1.0], [9.0]], np.float32),
                         np.array([1.0, 1.0, 0.0], np.float32))
    assert float(scale[0]) >= 1e29


def test_batchstd_relative_dead_guard():
    """Within-config constant columns get the huge scale; varying ones keep a
    finite scale even at small magnitudes."""
    rng = np.random.default_rng(1)
    n = 128
    X = jnp.asarray(np.stack([
        rng.standard_normal(n),
        np.where(np.arange(n) < 64, 1.0, 0.0),     # constant in config 1
    ], 1).astype(np.float32))
    W = jnp.asarray(np.stack([
        np.ones(n),                                # config 0: all rows
        (np.arange(n) < 64).astype(np.float64),    # config 1: first half
    ]))
    bs = _BatchStd(X, W)
    scale = np.asarray(bs.scale)
    assert scale[0, 1] < 1e3                       # varies under config 0
    assert scale[1, 1] >= 1e29                     # constant under config 1
    assert scale[1, 0] < 1e3


def test_time_period_list_row_path_locks_width():
    """ADVICE r3: the row-wise path must emit a fixed width even before any
    columnar batch, and numpy-array rows must not break the columnar lock."""
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.feature.dates import TimePeriodListTransformer
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import DateList

    t = TimePeriodListTransformer(period="DayOfWeek")
    t.set_input(FeatureBuilder.DateList("d").extract_field().as_predictor())
    r1 = t.transform_fn([1577836800000, 1577923200000])
    assert len(r1) == 2 and t.width == 2
    r2 = t.transform_fn([1577836800000])
    assert len(r2) == 2 and r2[1] == -1.0

    # columnar lock from numpy-array rows (truthiness of arrays is ambiguous)
    t2 = TimePeriodListTransformer(period="DayOfWeek")
    t2.set_input(FeatureBuilder.DateList("d").extract_field().as_predictor())
    col = Column.of_values(
        DateList, [np.array([1577836800000, 1577923200000, 1578009600000]),
                   None])
    out = t2.transform_column(FeatureTable({"d": col}, 2))
    assert np.asarray(out.values).shape == (2, 3)
    assert t2.width == 3


def test_distributed_module_has_no_private_jax_imports():
    import transmogrifai_tpu.parallel.distributed as dmod
    src = inspect.getsource(dmod)
    assert "jax._src" not in src
    # idempotent in-process
    dmod.initialize()
    dmod.initialize()


def test_gbt_sweep_leaf_clamp_keeps_small_parents():
    """The sweep-leaf noise clamp is parent-relative: H=1 under a parent of
    H=30 (min_child_weight territory) survives; H below bf16 noise of a huge
    parent is zeroed. Reproduces the clamp arithmetic on the (Tb, L) layout
    used in models/trees.py round_step."""
    lam = 0.1
    h_leaf = jnp.asarray(np.array([[1.0, 29.0, 0.5, 1000.0]], np.float32))
    g_leaf = jnp.asarray(np.array([[-0.5, 3.0, 2.0, -10.0]], np.float32))
    L_ = 4
    h_sib = h_leaf.reshape(-1, L_ // 2, 2)[..., ::-1].reshape(h_leaf.shape)
    h_parent = h_leaf + h_sib
    raw = -g_leaf / (h_leaf + lam + 1e-12)
    leaf = np.asarray(jnp.where(h_leaf < 2 ** -8 * h_parent,
                                jnp.zeros_like(raw), raw))
    assert leaf[0, 0] != 0.0       # H=1 under parent 30: alive
    assert leaf[0, 1] != 0.0
    assert leaf[0, 2] == 0.0       # H=0.5 under parent 1000.5: noise, zeroed
    assert leaf[0, 3] != 0.0


# -- round-4 VERDICT items: serve fusion, LOCO vectorization, mesh honesty ---

def _tiny_binary_table(n=96, seed=3):
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import OPVector, RealNN
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return FeatureTable({
        "label": Column(RealNN, y),
        "vec": Column(OPVector, X),
    }, n), X, y


def _fit_selected_model(models=None):
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.selector.model_selector import ModelSelector
    tbl, X, y = _tiny_binary_table()
    label = FeatureBuilder.RealNN("label").extract(lambda r: r["label"]) \
        .as_response()
    vec_f = FeatureBuilder.OPVector("vec").extract(lambda r: r["vec"]) \
        .as_predictor()
    sel = ModelSelector("binary", models=models, splitter=None)
    model = sel.set_input(label, vec_f).fit(tbl)
    return model, tbl


@pytest.mark.parametrize("models", [
    [("OpLogisticRegression", [{"regParam": 0.01, "elasticNetParam": 0.0}])],
    [("OpGBTClassifier", [{"maxDepth": 3, "minInstancesPerNode": 1,
                           "minInfoGain": 0.0, "maxIter": 5,
                           "stepSize": 0.3}])],
])
def test_selected_model_device_columnar_matches_transform(models):
    """The fused Prediction emission (device_columnar) must equal the plain
    transform_column matrix exactly (VERDICT r3 missing #4)."""
    import jax.numpy as jnp
    model, tbl = _fit_selected_model(models)
    assert model.device_fusable
    plain = np.asarray(model.transform_column(tbl).values)
    X = jnp.asarray(np.asarray(tbl["vec"].values, np.float32))
    vals, mask = model.device_columnar({model.device_inputs()[0]: (X, None)})
    assert mask is None
    np.testing.assert_allclose(np.asarray(vals), plain, rtol=1e-6, atol=1e-6)


def test_compiled_score_includes_model_stage():
    """compiled_score_function fuses the SelectedModel: no tail host stages
    remain for a numeric pipeline, and the output column keeps the
    Prediction type + keys metadata."""
    from transmogrifai_tpu.local.scoring import compiled_score_function
    from transmogrifai_tpu.types import Prediction
    model, tbl = _fit_selected_model()
    out_f = model.get_output()

    class _WrapModel:
        stages = [model]
        result_features = [out_f]

        def score(self, table):  # pragma: no cover - fallback path
            raise AssertionError("fusion should have engaged")

    fn = compiled_score_function(_WrapModel())
    scored = fn(tbl)
    col = scored[out_f.name]
    assert col.feature_type is Prediction
    keys = col.metadata.get("keys")
    assert keys and keys[0] == "prediction"
    plain = np.asarray(model.transform_column(tbl).values)
    np.testing.assert_allclose(np.asarray(col.values), plain,
                               rtol=1e-6, atol=1e-6)


def test_loco_topk_maps_lazy_and_correct():
    """Vectorized LOCO assembly: lazy TopKMaps match an eagerly-built
    per-row dict construction (VERDICT r3 weak #4)."""
    from transmogrifai_tpu.insights.record_insights import (
        RecordInsightsLOCO, TopKMaps)
    model, tbl = _fit_selected_model()
    vec_feature = model.input_features[-1]
    loco = RecordInsightsLOCO(model, top_k=3)
    loco.set_input(vec_feature)
    col = loco.transform_column(tbl)
    assert isinstance(col.values, TopKMaps)
    n = len(col.values)
    dense = np.asarray(col.values)
    assert dense is np.asarray(col.values)  # cached materialization
    for i in (0, n // 2, n - 1):
        d = col.values[i]
        assert isinstance(d, dict) and len(d) <= 3
        assert d == dense[i]
        # descending |contribution| insertion order
        mags = [abs(v) for v in d.values()]
        assert mags == sorted(mags, reverse=True)


def test_mesh_fold_sliced_eval_cap_applies():
    """Under a mesh, fold-sliced scoring (and so max_eval_rows) now applies:
    mesh sweep == single-device sweep metrics (VERDICT r3 weak #2)."""
    import jax
    from jax.sharding import Mesh
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    import transmogrifai_tpu.models.linear  # noqa: F401
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    n, d = 2048, 8
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    fam = MODEL_REGISTRY["OpLogisticRegression"]
    models = [(fam, [{"regParam": 0.01, "elasticNetParam": 0.0},
                     {"regParam": 0.1, "elasticNetParam": 0.5}])]

    cv0 = OpCrossValidation(num_folds=3, seed=0, max_eval_rows=256)
    best0 = cv0.validate(models, jnp.asarray(X), jnp.asarray(y), "binary",
                         "AuROC", True, 2)

    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    with Mesh(devs, ("data", "model")) as mesh:
        cv1 = OpCrossValidation(num_folds=3, seed=0, max_eval_rows=256,
                                mesh=mesh)
        best1 = cv1.validate(models, jnp.asarray(X), jnp.asarray(y), "binary",
                             "AuROC", True, 2)
    np.testing.assert_allclose(best0.results[0].fold_metrics,
                               best1.results[0].fold_metrics,
                               rtol=1e-5, atol=1e-5)
    assert best0.hyper == best1.hyper
