"""The regression selector's fits against the plain reference
(``benchmark/reference_regression.py``: numpy, float64, nothing of the
program imported), on the CPU at small sizes (PR 30): every stock linear and
generalised-linear point at its optimum, the moment passes by blocks, the
regressor forests against the numpy descent, the metrics, and the schedules
the program had before (60 ISTA steps, IRLS from zero) as controls that the
same limits fail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_regression as rr
from transmogrifai_tpu.models import glm, linear, trees  # noqa: F401
from transmogrifai_tpu.models.api import MODEL_REGISTRY, FittedParams
from transmogrifai_tpu.ops.metrics import regression_metrics_masked

#: standardised-coefficient distance from the float64 optimum that every
#: stock point keeps at this size (readings 2e-7 to 9e-5) and every control
#: below fails (3e-3 and more)
LIMIT = 5e-4
STOCK_LINEAR = [(0.001, 0.0), (0.001, 0.5), (0.01, 0.5), (0.1, 0.5)]


@pytest.fixture(scope="module")
def table():
    """4 000 rows: correlated reals of unlike scales (one a longitude), a
    three-level one-hot group whose columns sum to one, a positive
    heavy-tailed label."""
    rng = np.random.default_rng(30)
    n = 4000
    group = rng.choice(3, n, p=[0.7, 0.25, 0.05])
    dist = np.exp(0.6 + 0.8 * rng.standard_normal(n))
    X = np.stack([
        dist, 0.7 * dist + rng.standard_normal(n),
        -73.97 + 0.04 * rng.standard_normal(n), rng.standard_normal(n),
        rng.integers(0, 6, n), np.sin(rng.uniform(0, 6.28, n)),
        group == 0, group == 1, group == 2], axis=1).astype(np.float32)
    y = (2.5 + 3.0 * dist + np.array([0.0, 6.0, -2.0])[group]
         + 0.8 * X[:, 5]) * np.exp(0.15 * rng.standard_normal(n))
    folds = rng.integers(0, 3, n)
    W = np.stack([(folds != f).astype(np.float32) for f in range(3)])
    return X, y.astype(np.float32), W


def _std_diff(coef, fit):
    return float(np.abs((np.asarray(coef, np.float64) - fit["coef"])
                        * fit["std"]).max())


# -- the linear points ---------------------------------------------------------

@pytest.mark.parametrize("reg,alpha", STOCK_LINEAR)
def test_linear_point_is_at_its_float64_optimum(table, reg, alpha):
    X, y, W = table
    coef, bias = linear._fit_linreg_batch(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(W),
        jnp.full((3,), reg), jnp.full((3,), alpha))
    for lane in range(3):
        rows = W[lane] > 0
        fit = rr.fit_linear(X[rows], y[rows], reg, alpha)
        assert _std_diff(coef[lane], fit) < LIMIT
        assert abs(float(bias[lane]) - fit["bias"]) < 1e-2
        if alpha:
            assert fit["iterations"] > 0
            zero = fit["coef"] == 0
            assert (np.abs(np.asarray(coef[lane])[zero] * fit["std"][zero])
                    < LIMIT).all()


def test_one_lane_is_the_batch_s_lane(table):
    X, y, W = table
    args = (jnp.asarray(X), jnp.asarray(y))
    coef, bias = linear._fit_linreg_batch(
        *args, jnp.asarray(W), jnp.full((3,), 0.01), jnp.full((3,), 0.5))
    one, b1 = linear._fit_linreg(*args, jnp.asarray(W[1]), 0.01, 0.5)
    fit = rr.fit_linear(X[W[1] > 0], y[W[1] > 0], 0.01, 0.5)
    assert _std_diff(one, fit) < LIMIT and _std_diff(coef[1], fit) < LIMIT
    assert abs(float(b1) - float(bias[1])) < 1e-3


@pytest.mark.parametrize("fit", ["linear", "glm"])
def test_moments_by_blocks_are_one_block_s(table, fit, monkeypatch):
    X, y, W = table
    Xj, yj, Wj = jnp.asarray(X), jnp.asarray(y), jnp.asarray(W)

    def run():
        jax.clear_caches()
        if fit == "linear":
            return linear._fit_linreg_batch(Xj, yj, Wj, jnp.full((3,), 0.01),
                                            jnp.asarray([0.0, 0.5, 0.5]))
        return glm._fit_glm_batch(Xj, yj, Wj, jnp.full((3,), 0.01),
                                  jnp.asarray([0.0, 1.0, 1.0]),
                                  jnp.full((3,), 1.5))

    assert linear.gram_block_rows(X.shape[0], X.shape[1] + 2) == X.shape[0]
    whole = run()
    # 121 elements a row: blocks of 256 rows, the last moved back to end at n
    monkeypatch.setattr(linear, "_GRAM_BLOCK_ELEMS", 256 * 121)
    assert linear.gram_block_rows(X.shape[0], X.shape[1] + 2) == 256
    blocks = run()
    jax.clear_caches()
    for a, b in zip(whole, blocks):
        scale = np.abs(np.asarray(a)).max()
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-4 * scale


def test_block_rows_keep_to_the_element_budget():
    for n, width in [(4_194_304, 32), (4_194_304, 107), (1000, 32),
                     (4_194_304, 2002)]:
        rows = linear.gram_block_rows(n, width)
        assert rows == n or rows * width * width <= linear._GRAM_BLOCK_ELEMS \
            or rows == 8
        assert rows <= n
    assert linear.gram_block_rows(4_194_304, 32) == 32768


def test_ista_schedule_the_program_had_fails_the_same_limit(table):
    """A control: 60 ISTA steps at 1/trace stop short of every optimum with
    an L1 term; the ridge point is a closed form in both."""
    X, y, _ = table
    for reg, alpha in STOCK_LINEAR:
        far = _std_diff(rr.ista_linear(X, y, reg, alpha)["coef"],
                        rr.fit_linear(X, y, reg, alpha))
        assert (far > 8 * LIMIT) if alpha else (far < 1e-6)


def test_bf16_control_fails_the_same_limit(table):
    X, y, _ = table
    for reg, alpha in STOCK_LINEAR[:2]:
        assert _std_diff(rr.fit_linear(X, y, reg, alpha, "bf16")["coef"],
                         rr.fit_linear(X, y, reg, alpha)) > 4 * LIMIT
    assert _std_diff(rr.fit_glm(X, y, 0.001, rr.GAUSSIAN, "bf16")["coef"],
                     rr.fit_glm(X, y, 0.001, rr.GAUSSIAN)) > 2 * LIMIT


# -- the generalised-linear points ---------------------------------------------

GLM_FAMILY = MODEL_REGISTRY["OpGeneralizedLinearRegression"]


def _glm_fit(X, y, W, grid):
    garr = GLM_FAMILY.grid_to_arrays(grid)
    return GLM_FAMILY.fit_batch(jnp.asarray(X), jnp.asarray(y),
                                jnp.asarray(W), garr, 2)


@pytest.mark.parametrize("family,reg", [("gaussian", 0.001),
                                        ("gaussian", 0.1),
                                        ("poisson", 0.001),
                                        ("poisson", 0.2)])
def test_glm_point_is_at_its_float64_optimum(table, family, reg):
    X, y, W = table
    params = _glm_fit(X, y, W, [{"family": family, "regParam": reg}] * 3)
    for lane in range(3):
        rows = W[lane] > 0
        fit = rr.fit_glm(X[rows], y[rows], reg, rr.FAMILY_CODES[family])
        assert _std_diff(params["coef"][lane], fit) < LIMIT
        assert abs(float(params["bias"][lane]) - fit["bias"]) < 2e-2


def test_the_four_stock_poisson_points_fit_something(table):
    """Before PR 30 IRLS started at theta = 0: for a label whose mean is far
    from 1 all four points kept the zero vector and predicted exp(0)."""
    X, y, W = table
    grid = [g for g in GLM_FAMILY.default_grid("regression")
            if g["family"] == "poisson"]
    assert len(grid) == 4
    params = _glm_fit(X, y, np.ones((4, len(y)), np.float32), grid)
    pred = np.asarray(GLM_FAMILY.predict_batch(params, jnp.asarray(X), 2))
    rmse = [rr.rmse(p, y) for p in pred]
    assert max(rmse) < 0.8 * float(y.std())
    assert len({round(r, 4) for r in rmse}) == 4
    assert np.abs(pred - 1.0).max() > 1.0
    # the control: the schedule the program had predicts the constant 1
    old = rr.irls_from_zero(X, y, 0.01, rr.POISSON)
    assert np.abs(rr.predict(rr.GLM, old, X) - 1.0).max() == 0
    assert _std_diff(old["coef"], rr.fit_glm(X, y, 0.01, rr.POISSON)) \
        > 8 * LIMIT


# -- no lane holds a copy of the table ------------------------------------------

def _largest_value(jaxpr):
    biggest = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            biggest = max(biggest, int(np.prod(v.aval.shape or (1,))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            biggest = max(biggest, _largest_value(sub))
    return biggest


@pytest.mark.parametrize("fit,lanes", [("linear", 18), ("glm", 24)])
def test_no_lanes_by_rows_by_columns_array_at_the_cell_s_shapes(fit, lanes):
    n, d = 4_194_304, 30
    f32 = jnp.float32
    X = jax.ShapeDtypeStruct((n, d), f32)
    y = jax.ShapeDtypeStruct((n,), f32)
    W = jax.ShapeDtypeStruct((lanes, n), f32)
    g = jax.ShapeDtypeStruct((lanes,), f32)
    fn = (linear._fit_linreg_batch if fit == "linear"
          else glm._fit_glm_batch)
    closed = jax.make_jaxpr(fn)(X, y, W, *([g] * (2 if fit == "linear"
                                                else 3)))
    biggest = _largest_value(closed.jaxpr)
    # nothing the fit makes is larger than its inputs (the matrix, the
    # lanes' weights), let alone lanes x rows x columns
    assert biggest <= n * max(d, lanes)
    assert biggest * 16 < lanes * n * d


def test_the_ista_loop_and_the_lane_vmap_are_gone():
    import inspect
    assert "jax.vmap" not in inspect.getsource(glm)
    # naive Bayes keeps its own; nothing else in the module maps over lanes
    assert inspect.getsource(linear).count("jax.vmap") == 1
    assert "ista" not in inspect.getsource(linear).lower().replace(
        "fista", "")
    assert not hasattr(linear, "_standardize")
    assert not hasattr(glm, "_fit_glm")


def test_span_attrs_state_the_schedules():
    lin = MODEL_REGISTRY["OpLinearRegression"]
    grid = lin.default_grid("regression")
    assert lin.fit_span_attrs(1000, 30, grid, 2, True) == {
        "gramPasses": 2 + 1 + linear._REFINE_PASSES,
        "solveSteps": (1 + linear._REFINE_PASSES) * linear._ENET_STEPS}
    assert lin.fit_span_attrs(1000, 30, grid[:1], 2, False)["solveSteps"] == 0
    assert GLM_FAMILY.fit_span_attrs(1000, 30, [], 2, True) == {
        "irlsIters": glm._IRLS_ITERS, "gramPasses": glm._IRLS_ITERS + 3}


# -- the tree families against the numpy descent --------------------------------

@pytest.mark.parametrize("name", ["OpRandomForestRegressor",
                                  "OpGBTRegressor"])
def test_regressor_trees_predict_what_the_numpy_descent_does(table, name):
    X, y, _ = table
    X, y = X[:1024], y[:1024]
    fam = MODEL_REGISTRY[name]
    hyper = dict(fam.default_grid("regression")[3])
    hyper.update({"numTrees": 6} if "Forest" in name else {"maxIter": 4})
    params = fam.fit_batch(jnp.asarray(X), jnp.asarray(y),
                           jnp.ones((1, len(y)), jnp.float32),
                           fam.grid_to_arrays([hyper]), 2)
    fitted = FittedParams(name, fam.select_params(params, 0), hyper)
    got = np.asarray(fam.predict_parts(fitted, jnp.asarray(X))["prediction"])
    want = rr.predict(name, fitted.params, X)
    assert got.shape == want.shape == (1024,)
    assert np.abs(got - want).max() < 1e-4 * float(y.std())
    assert rr.rmse(got, y) < 0.8 * float(y.std())
    low = rr.predict(name, fitted.params, X, "bf16")
    assert np.abs(low - want).max() > 1e-2 * float(y.std())


# -- the evaluator's metrics ----------------------------------------------------

@pytest.mark.parametrize("metric,plain", [
    ("RootMeanSquaredError", rr.rmse), ("MeanSquaredError", rr.mse),
    ("MeanAbsoluteError", rr.mae), ("R2", rr.r2)])
def test_masked_regression_metrics_are_sparks(table, metric, plain):
    _, y, _ = table
    rng = np.random.default_rng(1)
    pred = (y + rng.standard_normal(len(y))).astype(np.float32)
    mask = rng.random(len(y)) < 0.7
    got = float(regression_metrics_masked(
        jnp.asarray(pred), jnp.asarray(y), jnp.asarray(mask))[metric])
    assert got == pytest.approx(plain(pred[mask], y[mask]), rel=2e-5)


# -- the timestamp stage at millions of rows ----------------------------------

@pytest.mark.parametrize("rows,span_ms,base_ms,path", [
    (20000, 31 * 86_400_000, 1_420_070_400_000, "table"),   # a month of trips
    (20000, 60 * 366 * 86_400_000, -30 * 366 * 86_400_000, "direct"),
    (3, 10 ** 12, 0, "direct"),
    (1, 5, 7, "direct"),
])
def test_unit_circle_block_is_the_per_period_encoding_to_the_bit(
        rows, span_ms, base_ms, path, monkeypatch):
    """One table an hour where the hours are few beside the rows, the
    direct path elsewhere: the same float32 either way, and the reference's
    own numbers."""
    from transmogrifai_tpu.impl.feature import dates
    rng = np.random.default_rng(rows)
    ms = (base_ms + rng.integers(0, span_ms, rows)).astype(np.int64)
    periods = dates.DEFAULT_CIRCULAR_PERIODS
    want = np.concatenate([dates.unit_circle(
        dates.time_period_values(ms, p), p) for p in periods], axis=1)
    sizes = []
    real = dates.time_period_values
    monkeypatch.setattr(dates, "time_period_values",
                        lambda v, p: sizes.append(len(v)) or real(v, p))
    got = dates.unit_circle_block(ms, periods)
    assert got.dtype == np.float32 and np.array_equal(got, want)
    assert (max(sizes) < rows) == (path == "table")
    for j, p in enumerate(periods):
        for k, part in enumerate(("sin", "cos")):
            assert np.array_equal(got[:, 2 * j + k],
                                  rr.unit_circle(ms, f"{p}_{part}"))


def test_unit_circle_block_of_no_rows_or_no_periods_is_empty():
    from transmogrifai_tpu.impl.feature import dates
    none = np.zeros(0, dtype=np.int64)
    assert dates.unit_circle_block(none, ("HourOfDay",)).shape == (0, 2)
    assert dates.unit_circle_block(np.arange(5), ()).shape == (5, 0)


# -- the one-hot stage hashes a column once a train ----------------------------

def _pivot(values, reps=100):
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.impl.feature import OneHotVectorizer
    from transmogrifai_tpu.types import PickList
    table = tg.FeatureTable.from_columns({"c": (PickList, values * reps)})
    st = OneHotVectorizer()
    st.set_input(FeatureBuilder.PickList("c").extract_field().as_predictor())
    return st, table


@pytest.mark.parametrize("values", [["a", "b", "a", None], [3, 1, 3, None],
                                    ["x", "y", "z", "x"]],
                         ids=["str", "ints", "no_null"])
def test_the_transform_of_the_fitted_table_takes_the_fits_codes(
        values, monkeypatch):
    from transmogrifai_tpu.impl.feature import vectorizers as vz
    passes = []
    real = vz._hash_pass
    monkeypatch.setattr(vz, "_hash_pass",
                        lambda v: passes.append(len(v)) or real(v))
    st, table = _pivot(values)
    model = st.fit(table)
    assert len(passes) == 1 and len(vz._FIT_CODES) >= 1
    same = np.asarray(model.transform_column(table).values)
    assert len(passes) == 1                      # the fit's codes, taken once
    assert id(np.asarray(table["c"].values)) not in vz._FIT_CODES
    again = np.asarray(model.transform_column(table).values)
    assert len(passes) == 2                      # hashed as before
    assert np.array_equal(same, again)
    _, other = _pivot(values[::-1])
    assert np.array_equal(np.asarray(model.transform_column(other).values),
                          again.reshape(100, len(values), -1)[:, ::-1]
                          .reshape(again.shape))
    assert len(passes) == 3


def test_fit_codes_are_not_taken_for_another_mask_and_die_with_the_array():
    import gc
    from transmogrifai_tpu.impl.feature import vectorizers as vz
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import PickList
    st, table = _pivot(["a", "b", "a", "c"])
    model = st.fit(table)
    vals = np.asarray(table["c"].values)
    masked = np.ones(len(vals), dtype=bool)
    masked[::4] = False
    got = np.asarray(model.transform_column(FeatureTable(
        {"c": Column(PickList, vals, masked)}, len(vals))).values)
    assert (got[::4, -1] == 1).all() and (got[::4, :-1] == 0).all()
    st.fit(table)
    key = id(vals)
    assert key in vz._FIT_CODES
    del table, vals, got
    gc.collect()
    assert key not in vz._FIT_CODES


def test_nothing_of_a_column_outlives_its_train():
    """A train hashes its columns (by object since PR 37) and keeps none of
    it: no codes, levels or pointers left in ``_FIT_CODES`` or in any other
    module-level container of the vectorizers, nothing hung on a column, and
    a second train of the same table counts every column again."""
    import pandas as pd
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.impl.feature import vectorizers as vz
    from transmogrifai_tpu.workflow import OpWorkflow
    rng = np.random.RandomState(37)
    levels = np.array([f"level{i}" for i in range(9)], dtype=object)
    # object columns said to be such: a frame left to infer a string dtype
    # hands out a new object a row
    df = pd.DataFrame({
        "c1": pd.Series(levels[rng.choice(9, 600)], dtype=object),
        "c2": pd.Series(levels[rng.choice(3, 600)], dtype=object),
        "x": rng.randn(600)})
    feats = [FeatureBuilder.PickList("c1").extract_field().as_predictor(),
             FeatureBuilder.PickList("c2").extract_field().as_predictor(),
             FeatureBuilder.Real("x").extract_field().as_predictor()]
    wf = OpWorkflow().set_input_dataset(df).set_result_features(
        tg.transmogrify(feats))

    def held():
        return {k: len(v) for k, v in vars(vz).items()
                if isinstance(v, (dict, list, set))}
    counted = []
    real = vz._factorize_valid

    def counting(vals, m):
        got = real(vals, m)
        counted.append(got[3])
        return got
    vz._factorize_valid = counting
    try:
        before = held()
        model = wf.train()
        assert vz._FIT_CODES == {} and held() == before
        assert counted == [9, 3]            # one object a level, both columns
        table = model.train_table
        for name in table.column_names:
            assert set(vars(table[name])) == {"feature_type", "values",
                                              "mask", "metadata"}
        wf.train()
        assert counted == [9, 3, 9, 3] and vz._FIT_CODES == {}
    finally:
        vz._factorize_valid = real
