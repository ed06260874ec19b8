"""The multiclass path against the plain reference
(``benchmark/reference_multiclass.py``: numpy, float64) at small size on
seeded data: the softmax solver reaches the optimum of its objective, with
and without an L1 term, whatever the chunk of lanes; the limits that a sound
refit keeps fail a bfloat16 fit and the 200-step Adam schedule the solver
replaced; a C-class forest scores as the numpy descent does; the sweep's
weighted F1 is Spark's; and the spans carry what the per-layer metrics
read."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_multiclass as ref
from transmogrifai_tpu.models import linear, trees
from transmogrifai_tpu.models.api import MODEL_REGISTRY
from transmogrifai_tpu.ops.metrics import multiclass_metrics_masked

SHARES = (0.57, 0.22, 0.19, 0.012, 0.005, 0.003)     # three hold 98 %


def make(n, seed, classes=len(SHARES), reals=6, strength=0.7):
    """Rows whose columns shift with the class: ``reals`` normal columns,
    a 5-level and a 2-level one-hot group; every class has 12 rows or
    more."""
    rng = np.random.default_rng(seed)
    rule = np.random.default_rng(99)
    counts = np.maximum(np.round(np.array(SHARES[:classes]) * n), 12)
    counts[0] += n - counts.sum()
    y = np.repeat(np.arange(classes), counts.astype(int))
    rng.shuffle(y)
    cols = [(rng.standard_normal(n) + rule.normal(0, strength, classes)[y])
            for _ in range(reals)]
    for k in (5, 2):
        p = np.exp(rule.normal(0, strength, (classes, k)))
        cdf = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
        code = (rng.random(n)[:, None] > cdf[y]).sum(axis=1).clip(0, k - 1)
        cols += list(np.eye(k)[code].T[:k - 1 if k == 2 else k])
    return np.column_stack(cols).astype(np.float32), y.astype(np.int64)


def fit(X, y, points, classes, sweep=False, lane_chunk=None, folds=None):
    """The program's batched fit of ``points`` [(regParam, elasticNet)]."""
    W_rows = (np.ones((len(points), len(y)), np.float32) if folds is None
              else folds)
    W, b = linear._fit_softmax_batch(
        jnp.asarray(X), jnp.asarray(y, jnp.int32), jnp.asarray(W_rows),
        jnp.asarray([p[0] for p in points], jnp.float32),
        jnp.asarray([p[1] for p in points], jnp.float32), classes,
        sweep=sweep, lane_chunk=lane_chunk)
    return np.asarray(W, np.float64), np.asarray(b, np.float64)


def refit_numbers(W, b, opt, X):
    """(coefficients in standardised units, intercepts, probabilities):
    largest distance of a fit from the reference's optimum."""
    Wc, bc = ref.centred(W, b)
    return (np.abs((Wc - opt["W"]) * opt["std"][:, None]).max(),
            np.abs(bc - opt["b"]).max(),
            np.abs(ref.softmax_prob(X, W, b)
                   - ref.softmax_prob(X, opt["W"], opt["b"])).max())


@pytest.fixture(scope="module")
def data():
    X, y = make(6000, 5)
    return X, y, ref.fit_softmax(X, y, 0.01, len(SHARES))


#: coefficients, intercepts, probabilities. Readings on three seeded tables:
#: sound up to 9e-6 / 2.1e-5 / 4e-6; 200 Adam steps at least 3.6e-3 / 3.6e-2
#: / 5.9e-4; a bfloat16 fit at least 1.1e-3 / 6.9e-4 / 2.2e-3
LIMITS = (1e-4, 5e-4, 5e-5)


def test_the_refit_reaches_the_float64_optimum(data):
    X, y, opt = data
    assert opt["grad_max"] < 1e-10
    W, b = fit(X, y, [(0.01, 0.0)], len(SHARES))
    got = refit_numbers(W[0], b[0], opt, X)
    assert all(g < lim / 5 for g, lim in zip(got, LIMITS)), got
    assert abs(b[0].sum()) < 1e-4          # intercepts come out centred


@pytest.mark.parametrize("control", ["bf16", "adam"])
def test_the_limits_a_sound_refit_keeps_fail_the_controls(data, control):
    X, y, opt = data
    low = (ref.fit_softmax(X, y, 0.01, len(SHARES), "bf16", max_iter=8,
                           cg_iter=30) if control == "bf16"
           else ref.adam_softmax(X, y, 0.01, len(SHARES)))
    coef, _, prob = refit_numbers(low["W"], low["b"], opt, X)
    assert coef > 5 * LIMITS[0] and prob > 5 * LIMITS[2], (coef, prob)


def test_the_sweep_schedule_ranks_as_the_optimum_does(data):
    X, y, opt = data
    W, b = fit(X, y, [(0.01, 0.0)], len(SHARES), sweep=True)
    coef, _, prob = refit_numbers(W[0], b[0], opt, X)
    assert LIMITS[0] < coef < 0.1 and prob < 0.05      # bfloat16, not float32
    f1 = [ref.weighted_f1(ref.softmax_prob(X, w, c).argmax(axis=1), y,
                          len(SHARES)) for w, c in ((W[0], b[0]),
                                                    (opt["W"], opt["b"]))]
    assert abs(f1[0] - f1[1]) < 5e-3


@pytest.mark.parametrize("point", [(0.01, 0.5), (0.1, 0.5), (0.2, 0.5)])
def test_an_elastic_net_point_agrees_with_a_proximal_gradient_fit(point):
    X, y = make(2500, 8, classes=4, reals=4)
    want = ref.fit_elastic_net(X, y, *point, 4, steps=4000)
    W, b = fit(X, y, [point], 4)
    assert np.abs((W[0] - want["W"]) * want["std"][:, None]).max() < 2e-4
    assert np.abs((b[0] - b[0].mean()) - want["b"]).max() < 1e-3
    # an L1 term leaves exact zeros, and the same ones
    assert ((W[0] == 0) == (want["W"] == 0)).all() and (W[0] == 0).any()


STOCK = [(r, e) for r in (0.01, 0.1, 0.2) for e in (0.0, 0.5)]


def test_the_six_stock_points_give_six_fits_whatever_the_chunk(data):
    X, y, _ = data
    rng = np.random.default_rng(3)
    folds = (rng.integers(0, 3, len(y))[None, :]
             != (np.arange(6) % 3)[:, None]).astype(np.float32)
    W, b = fit(X, y, STOCK, len(SHARES), folds=folds)
    for i in range(6):
        for j in range(i):
            assert np.abs(W[i] - W[j]).max() > 1e-2, (STOCK[i], STOCK[j])
    for chunk in (1, 4):                 # 6 chunks of 1; 4 + 2 padded to 4
        Wc, bc = fit(X, y, STOCK, len(SHARES), lane_chunk=chunk, folds=folds)
        assert np.abs(Wc - W).max() < 1e-5 and np.abs(bc - b).max() < 1e-5
    assert linear.softmax_lane_chunk(1048576, 18, 23) == 9
    assert linear.softmax_lane_chunk(1000, 18, 23) == 18


def test_the_sweep_path_does_not_depend_on_the_chunk_beyond_bfloat16(data):
    X, y, _ = data
    W, b = fit(X, y, STOCK[:4], len(SHARES), sweep=True)
    Wc, bc = fit(X, y, STOCK[:4], len(SHARES), sweep=True, lane_chunk=1)
    for i in range(4):
        f1 = [ref.weighted_f1(ref.softmax_prob(X, w[i], c[i]).argmax(axis=1),
                              y, len(SHARES)) for w, c in ((W, b), (Wc, bc))]
        assert abs(f1[0] - f1[1]) < 0.01


@pytest.mark.parametrize("depth", [3, 12])
def test_a_forests_class_probabilities_are_the_numpy_descents(depth):
    X, y = make(1500, 11, classes=5, reals=5)
    family = MODEL_REGISTRY["OpRandomForestClassifier"]
    grid = [{"maxDepth": depth, "minInstancesPerNode": 5, "minInfoGain": 0.0,
             "numTrees": 3, "subsamplingRate": 1.0}]
    params = family.fit_batch(
        jnp.asarray(X), jnp.asarray(y, jnp.float32),
        jnp.ones((1, len(y)), jnp.float32), family.grid_to_arrays(grid), 5)
    from transmogrifai_tpu.models.api import FittedParams
    fitted = FittedParams(family=family.name,
                          params=family.select_params(params, 0),
                          hyper=grid[0], num_classes=5)
    got = family.predict_one(fitted, jnp.asarray(X))["probability"]
    want = ref.forest_prob(X, fitted.params, 5)
    assert got.shape == want.shape == (1500, 5)
    assert np.abs(got - want).max() < 1e-6
    assert np.allclose(want.sum(axis=1), 1.0, atol=1e-5)
    low = ref.forest_prob(X, fitted.params, 5, "bf16")
    assert np.abs(low - want).max() > 1e-3          # the control differs


def test_the_sweeps_f1_is_sparks_weighted_f_measure():
    rng = np.random.default_rng(0)
    C, n = 7, 4000
    y = rng.choice(C, n, p=[.5, .2, .2, .05, .03, .015, .005])
    pred = np.where(rng.random(n) < 0.8, y, rng.choice(C - 1, n))  # no 6
    mask = rng.random(n) < 0.6
    got = multiclass_metrics_masked(jnp.asarray(pred, jnp.int32),
                                    jnp.asarray(y, jnp.int32),
                                    jnp.asarray(mask), C)
    assert float(got["F1"]) == pytest.approx(
        ref.weighted_f1(pred[mask], y[mask], C), abs=1e-6)
    assert ref.weighted_f1(y, y, C) == 1.0
    # by hand: two classes, one row of class 1 taken for class 0
    assert ref.weighted_f1([0, 0, 0, 1], [0, 0, 1, 1], 2) == pytest.approx(
        0.5 * 0.8 + 0.5 * (2 / 3))


def test_span_attributes_come_from_the_solvers_own_rules(monkeypatch):
    lr = MODEL_REGISTRY["OpLogisticRegression"]
    grid = lr.default_grid("multiclass") * 3
    attrs = lr.fit_span_attrs(1048576, 76, grid, 23, True)
    newton, cg = linear._SOFTMAX_SCHEDULE[True]
    assert attrs == {"contractions": 1 + newton * (4 + 2 * cg + len(
        linear._SOFTMAX_STEPS)), "laneChunks": 2}
    assert lr.fit_span_attrs(1048576, 76, grid[:1], 23, False) == {
        "contractions": linear.softmax_contractions(False), "laneChunks": 1}
    # the binary fit gives its schedule's reads of the matrix instead
    assert lr.fit_span_attrs(1048576, 76, grid, 2, True) == {
        "matrixPasses": 5 + 8 * (4 + 2 * 6)}
    assert lr.fit_span_attrs(1048576, 76, grid, 2, False) == {
        "matrixPasses": linear.logreg_matrix_passes(False)}
    # and so does the linear SVC: a standardisation and two products a step
    svc = MODEL_REGISTRY["OpLinearSVC"]
    for sweep, before_the_loop in ((True, 5), (False, 3)):
        assert svc.fit_span_attrs(1048576, 76, grid, 2, sweep) == {
            "matrixPasses": before_the_loop + 2 * 100}

    # the forest's chunk count against what its growers' lax.map really get
    rf = MODEL_REGISTRY["OpRandomForestClassifier"]
    grid = rf.default_grid("multiclass") * 3
    seen = []
    real_map = jax.lax.map

    def spy(f, xs, **kw):
        seen.append(jax.tree_util.tree_leaves(xs)[0].shape[0])
        return real_map(f, xs, **kw)

    monkeypatch.setattr(jax.lax, "map", spy)
    # ... and the compact column width against what the growers are handed
    widths = set()
    for name in ("_grow_forest", "_grow_forest_capped"):
        def grower(*a, _real=getattr(trees, name), feat_idx=None, **kw):
            widths.add(0 if feat_idx is None else feat_idx.shape[1])
            return _real(*a, feat_idx=feat_idx, **kw)
        monkeypatch.setattr(trees, name, grower)
    n, d, C = 20000, 30, 23
    garr = {k: np.asarray(v) for k, v in rf.grid_to_arrays(grid).items()}
    jax.eval_shape(
        lambda X, y, W: rf.sweep_fit_batch(X, y, W, garr, C),
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((len(grid), n), jnp.float32))
    attrs = rf.fit_span_attrs(n, d, grid, C, True)
    assert attrs == {"configChunks": sum(seen), "featSubset": attrs[
        "featSubset"], "histShards": 8, "combine": "fused",
        "sampleRows": min(n, 8192)}
    assert widths == {attrs["featSubset"]} and 0 < attrs["featSubset"] < d
    # a table too narrow for a strict subset runs full width: 0
    assert rf.fit_span_attrs(n, 6, grid, C, True)["featSubset"] == 0
    # at train-kddcup99's shape: three depth groups of 18 lanes, the deep
    # one (64 sweep slots x 23 planes) the most chunked; at the refit's 256
    # slots a configuration would be a chunk of its own
    at_cell = rf.fit_span_attrs(1048576, 76, grid, 23, True)["configChunks"]
    assert 3 < at_cell <= 54
    assert trees._rf_config_chunk(18, 8192, 16, 12, 256, 23, 76, 32) == 1


def test_a_multiclass_train_puts_its_shape_on_the_spans():
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.impl.selector import factories
    from transmogrifai_tpu.observability import trace as obs
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.workflow import OpWorkflow
    X, y = make(1500, 2, classes=4, reals=4)
    valid = np.ones(len(y), bool)
    cols = {f"x{i}": Column(T.Real, X[:, i], valid) for i in range(4)}
    cols["y"] = Column(T.RealNN, y.astype(np.float32), valid)
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(f"x{i}").extract_field().as_predictor()
             for i in range(4)]
    from transmogrifai_tpu import transmogrify
    sel = factories.MultiClassificationModelSelector.with_cross_validation(
        models=[("OpLogisticRegression",
                 [{"regParam": 0.01, "elasticNetParam": 0.0},
                  {"regParam": 0.1, "elasticNetParam": 0.5}])])
    pred = sel.set_input(label, transmogrify(feats)).get_output()
    obs.enable_tracing(True)
    obs.tracer().clear()
    try:
        OpWorkflow().set_input_table(FeatureTable(cols, len(y))) \
            .set_result_features(pred).train()
        spans = {s.name: s for s in obs.tracer().finished()}
    finally:
        obs.enable_tracing(False)
    sweep = spans["sweep.family"].attrs
    assert (sweep["classes"], sweep["lanes"], sweep["rows"]) == (4, 6, 1350)
    assert sweep["contractions"] == linear.softmax_contractions(True)
    assert sweep["laneChunks"] == 1 and sweep["features"] >= 4
    refit = spans["selector.refit"].attrs
    assert (refit["classes"], refit["lanes"], refit["rows"]) == (4, 1, 1350)
    assert refit["contractions"] == linear.softmax_contractions(False)
    prepare = spans["selector.prepare"].attrs
    assert (prepare["labelsKept"], prepare["rowsKept"]) == (4, 1350)
