"""Tree family tests: DT / RF / GBT / XGBoost, classification + regression.

Mirrors the reference contract specs for its tree wrappers
(reference: core/src/test/.../OpRandomForestClassifierTest.scala,
OpGBTClassifierTest.scala, OpXGBoostClassifierTest.scala etc.): fit on
synthetic data, check predictions beat chance, check batch/one parity.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from transmogrifai_tpu.models.api import MODEL_REGISTRY
import transmogrifai_tpu.models.trees  # noqa: F401 (registers families)


def _binary_data(n=400, d=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    # nonlinear decision rule trees can learn but linear models can't fully
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0.5)).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _regression_data(n=400, d=6, seed=1):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (np.where(X[:, 0] > 0, 3.0, -1.0) + 0.5 * np.abs(X[:, 1])
         + 0.05 * rng.randn(n)).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _multiclass_data(n=450, d=6, seed=2, C=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = ((X[:, 0] > 0).astype(int) + 2 * (X[:, 1] > 0).astype(int))
    y = np.minimum(y, C - 1).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _acc(scores, y, num_classes):
    s = np.asarray(scores)
    if s.ndim == 2 and num_classes > 2:
        pred = s.argmax(-1)
    else:
        pred = (s > 0.5).astype(int)
    return (pred == np.asarray(y)).mean()


GRID_TREE = [{"maxDepth": 4, "minInstancesPerNode": 5, "minInfoGain": 0.001}]
GRID_RF = [{**GRID_TREE[0], "numTrees": 10, "subsamplingRate": 1.0}]
GRID_GBT = [{**GRID_TREE[0], "maxIter": 10, "stepSize": 0.3}]
GRID_XGB = [{"maxDepth": 4, "maxIter": 15, "stepSize": 0.3,
             "minChildWeight": 1.0, "lambda": 1.0, "minInfoGain": 0.0,
             "minInstancesPerNode": 0.0}]


@pytest.mark.parametrize("fam_name,grid", [
    ("OpDecisionTreeClassifier", GRID_TREE),
    ("OpRandomForestClassifier", GRID_RF),
    ("OpGBTClassifier", GRID_GBT),
    ("OpXGBoostClassifier", GRID_XGB),
])
def test_binary_classifiers_learn_xor(fam_name, grid):
    X, y = _binary_data()
    fam = MODEL_REGISTRY[fam_name]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((len(grid), X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, num_classes=2)
    scores = fam.predict_batch(params, X, 2)
    assert scores.shape == (len(grid), X.shape[0])
    acc = _acc(scores[0], y, 2)
    assert acc > 0.9, f"{fam_name} train accuracy {acc}"


@pytest.mark.parametrize("fam_name,grid", [
    ("OpDecisionTreeRegressor", GRID_TREE),
    ("OpRandomForestRegressor", GRID_RF),
    ("OpGBTRegressor", GRID_GBT),
    ("OpXGBoostRegressor", GRID_XGB),
])
def test_regressors_fit_step_function(fam_name, grid):
    X, y = _regression_data()
    fam = MODEL_REGISTRY[fam_name]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((len(grid), X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, num_classes=2)
    pred = np.asarray(fam.predict_batch(params, X, 2))[0]
    base = float(np.var(np.asarray(y)))
    mse = float(np.mean((pred - np.asarray(y)) ** 2))
    assert mse < 0.3 * base, f"{fam_name} mse {mse} vs var {base}"


@pytest.mark.parametrize("fam_name,grid", [
    ("OpDecisionTreeClassifier", GRID_TREE),
    ("OpRandomForestClassifier", GRID_RF),
    ("OpXGBoostClassifier", GRID_XGB),
])
def test_multiclass(fam_name, grid):
    X, y = _multiclass_data()
    fam = MODEL_REGISTRY[fam_name]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((len(grid), X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, num_classes=3)
    scores = fam.predict_batch(params, X, 3)
    assert scores.shape == (len(grid), X.shape[0], 3)
    acc = _acc(scores[0], y, 3)
    assert acc > 0.85, f"{fam_name} multiclass accuracy {acc}"


def test_fold_weights_exclude_rows():
    """Rows with weight 0 must not influence the fit: two configs whose
    train halves are disjoint give different trees."""
    X, y = _binary_data(n=300)
    fam = MODEL_REGISTRY["OpDecisionTreeClassifier"]
    garr = fam.grid_to_arrays(GRID_TREE * 2)
    n = X.shape[0]
    w = np.ones((2, n), np.float32)
    w[0, : n // 2] = 0.0
    w[1, n // 2:] = 0.0
    params = fam.fit_batch(X, y, jnp.asarray(w), garr, num_classes=2)
    leaves = np.asarray(params["leaf"])
    assert not np.allclose(leaves[0], leaves[1])


def test_predict_one_matches_batch():
    X, y = _binary_data(n=200)
    fam = MODEL_REGISTRY["OpGBTClassifier"]
    garr = fam.grid_to_arrays(GRID_GBT)
    w = jnp.ones((1, X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, num_classes=2)
    batch_scores = np.asarray(fam.predict_batch(params, X, 2))[0]
    from transmogrifai_tpu.models.api import FittedParams
    fitted = FittedParams(family=fam.name, params=fam.select_params(params, 0),
                          hyper=GRID_GBT[0], num_classes=2)
    parts = fam.predict_one(fitted, np.asarray(X))
    np.testing.assert_allclose(parts["probability"][:, 1], batch_scores,
                               rtol=1e-5, atol=1e-5)


def test_min_instances_prunes_splits():
    """A huge minInstancesPerNode must force a stump-ish tree."""
    X, y = _binary_data(n=200)
    fam = MODEL_REGISTRY["OpDecisionTreeClassifier"]
    grid = [{"maxDepth": 4, "minInstancesPerNode": 1000, "minInfoGain": 0.0}]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((1, X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, num_classes=2)
    thr = np.asarray(params["thresh"])[0]
    assert np.all(np.isinf(thr)), "no split should satisfy minInstances=1000"


def test_max_depth_respected():
    """maxDepth=1 config inside a deeper static build: only root splits."""
    X, y = _binary_data(n=300)
    fam = MODEL_REGISTRY["OpDecisionTreeClassifier"]
    grid = [{"maxDepth": 1, "minInstancesPerNode": 1, "minInfoGain": 0.0},
            {"maxDepth": 4, "minInstancesPerNode": 1, "minInfoGain": 0.0}]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((2, X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, num_classes=2)
    thr = np.asarray(params["thresh"])
    # config 0: heap nodes below the root (index >= 1) must all be +inf leaves
    assert np.isfinite(thr[0, 0])
    assert np.all(np.isinf(thr[0, 1:]))
    # config 1 actually uses the depth
    assert np.isfinite(thr[1, 1:3]).any()


def test_validator_sweep_with_trees():
    """Trees slot into the CV sweep exactly like linear families."""
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    X, _ = _binary_data(n=300)
    y = (np.asarray(X)[:, 0] > 0).astype(np.float32)  # axis-aligned rule
    fam = MODEL_REGISTRY["OpRandomForestClassifier"]
    grid = [{"maxDepth": 3, "minInstancesPerNode": 5, "minInfoGain": 0.001,
             "numTrees": 8, "subsamplingRate": 1.0},
            {"maxDepth": 4, "minInstancesPerNode": 5, "minInfoGain": 0.001,
             "numTrees": 8, "subsamplingRate": 1.0}]
    cv = OpCrossValidation(num_folds=2, seed=0)
    best = cv.validate([(fam, grid)], X, y, problem="binary",
                       metric_name="AuROC", larger_better=True, num_classes=2)
    assert best.family_name == "OpRandomForestClassifier"
    assert best.metric_value > 0.8
    assert best.results[0].fold_metrics.shape == (2, 2)


def test_grow_forest_leaf_stats_match_segment_sums():
    """The sweep-time leaf stats read off the final level's histogram
    (return_leaf_stats) equal the exact per-leaf segment sums over the
    routed sample — pins the j-major cumsum/interleave layout (round 3)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models.trees import (_diag_leaf_hist,
                                                _grow_forest)

    rng = np.random.RandomState(0)
    S, d, Tb, depth, n_bins = 512, 6, 4, 3, 8
    codes = jnp.asarray(rng.randint(0, n_bins, (S, d)), jnp.int32)
    edges = jnp.asarray(np.sort(rng.randn(d, n_bins - 1), 1), jnp.float32)
    # small integer-ish weights keep the bf16 histogram sums exact
    sw = [jnp.asarray(rng.randint(0, 3, (S, Tb)), jnp.float32)
          for _ in range(3)]
    fmasks = jnp.ones((Tb, d), bool)
    cfg = {"max_depth": jnp.full((Tb,), float(depth)),
           "min_instances": jnp.full((Tb,), 1.0),
           "min_info_gain": jnp.full((Tb,), 0.0),
           "lam": jnp.full((Tb,), 1e-6),
           "min_child_weight": jnp.zeros((Tb,))}
    fs, ths, bhs, node_s, lst = _grow_forest(
        codes, edges, sw, fmasks, cfg, depth=depth, n_bins=n_bins,
        mode="gh", return_leaf_stats=True)
    L = 2 ** depth
    A_cols = jnp.stack(sw, axis=1)                  # (S, 3, Tb)
    exact = _diag_leaf_hist(node_s, A_cols, L)      # (3, Tb, L)
    np.testing.assert_allclose(np.asarray(lst),
                               np.asarray(exact).transpose(1, 2, 0),
                               atol=1e-3, rtol=1e-3)

    # depth=0: root-leaf stats are the plain column sums
    _, _, _, _, lst0 = _grow_forest(
        codes, edges, sw, fmasks,
        {k: v for k, v in cfg.items()}, depth=0, n_bins=n_bins,
        mode="gh", return_leaf_stats=True)
    want = np.stack([np.asarray(s).sum(0) for s in sw], -1)[:, None, :]
    np.testing.assert_allclose(np.asarray(lst0), want, rtol=1e-5)


@pytest.mark.parametrize("mode,k", [("counts", 2), ("counts", 5), ("gh", 3)])
def test_grow_forest_compact_columns_grow_the_same_trees(mode, k):
    """Compact against full, complete-heap grower (sibling subtraction at
    every level): the same split column and bin at every heap node, equal
    thresholds, the same routing, and the final level's leaf statistics
    within float32 rounding — also for the tree that drew no column, whose
    node totals are read off a column that is no candidate."""
    from test_deep_trees import _subset_case
    from transmogrifai_tpu.models.trees import _grow_forest
    codes, edges, sw, fmasks, cfg, cols, n_bins, fm = _subset_case(
        mode, k, Tb=6, seed=3)

    def grow(**kw):
        return [np.asarray(a) for a in _grow_forest(
            codes, edges, sw, fmasks, cfg, depth=4, n_bins=n_bins,
            mode=mode, return_leaf_stats=True, **kw)]

    full, compact = grow(), grow(feat_idx=cols)
    for nm, a, b in zip(("feat", "thresh", "bins", "node_s"), full, compact):
        np.testing.assert_array_equal(a, b, err_msg=nm)
    np.testing.assert_allclose(compact[4], full[4], rtol=1e-6, atol=1e-4)
    assert (compact[2] < n_bins).any() and compact[4][3].sum() > 0
    assert not (compact[2][3] < n_bins).any()


@pytest.mark.parametrize("Tb", [1, 2, 5, 33, 70])
def test_diag_leaf_hist_is_the_segment_sum(Tb):
    """The leaf sums' tree block follows the tree count up to 32 (PR 43: a
    lone tree is a block of `_DIAG_MIN_BLOCK`, 33 and 70 take blocks of 64):
    whatever the block, out[j, t, l] is tree t's own sum of stat j over the
    rows in its leaf l, float32-exact against numpy in float64."""
    from transmogrifai_tpu.models import trees
    rng = np.random.RandomState(Tb)
    S, J, L = 777, 2, 16
    node = rng.randint(0, L, size=(S, Tb)).astype(np.int32)
    A = rng.randn(S, J, Tb).astype(np.float32)
    got = np.asarray(trees._diag_leaf_hist(jnp.asarray(node),
                                           jnp.asarray(A), L))
    want = np.zeros((J, Tb, L))
    for t in range(Tb):
        for j in range(J):
            want[j, t] = np.bincount(node[:, t], weights=A[:, j, t].astype(
                np.float64), minlength=L)
    assert got.shape == (J, Tb, L)
    # sums of some 50 numbers of either sign: the float32 rounding of a
    # sum that cancels is absolute
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    one = np.asarray(trees._diag_leaf_hist(
        jnp.asarray(node), jnp.asarray(A[:, 0]), L))       # (S, Tb) form
    np.testing.assert_array_equal(one, got[0])


def test_a_lone_boosted_tree_grows_as_in_company():
    """A boosted refit grows ONE tree a round (B = C = 1) on one tree lane
    and sums its leaves in a block of four; the same configuration fitted as
    33 copies side by side takes the 64-lane forms of both. The tables of
    copy 0 are the lone tree's: the masked operand's values are the same
    bfloat16 numbers and every lane sums the same rows in the same eight
    blocks."""
    from transmogrifai_tpu.models import trees
    X, y = _binary_data(n=1500, d=6, seed=43)
    n = X.shape[0]
    w = np.ones((1, n), np.float32)
    w[0, ::9] = 0.0                                       # a fold held out

    def fit(B):
        col = lambda v: jnp.full((B,), v, jnp.float32)
        return trees._fit_gbt_batch(
            X, y, jnp.asarray(np.repeat(w, B, axis=0)), col(5.0), col(5.0),
            col(0.0), col(4.0), col(0.3), col(0.0), col(0.0), depth=5,
            n_bins=32, num_classes=2, task="binary", n_rounds=4,
            sweep=False, n_slots=16)

    lone, company = fit(1), fit(33)
    for key in ("feat_lv", "bins_lv", "base_lv", "thresh_lv"):
        got, want = np.asarray(lone[key]), np.asarray(company[key])
        assert got.shape == (1,) + want.shape[1:] == (1, 4, 1, 5, 16)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(want[32], want[0])
    assert (np.asarray(lone["bins_lv"]) < 32).sum() > 30       # trees grew
    np.testing.assert_allclose(np.asarray(lone["leaf"])[0],
                               np.asarray(company["leaf"])[0],
                               rtol=0, atol=1e-6)


def test_a_lone_trees_program_holds_no_contraction_laid_out_for_dozens():
    """`_fit_gbt_batch` as `train-higgs`'s refit calls it (one binary
    configuration, depth 12, 256 slots, 20 rounds), lowered at test size: no
    level contraction is wider than 3 x 256 x 1 stat columns (they were
    3 x 256 x 32), and the leaf sums are 2 x 4 columns against 4 x 256
    (they were 2 x 64 against 64 x 256)."""
    import re
    from transmogrifai_tpu.models import trees
    X, y = _binary_data(n=1500, d=6, seed=43)
    col = lambda v: jnp.full((1,), v, jnp.float32)
    text = trees._fit_gbt_batch.lower(
        X, y, jnp.ones((1, 1500), jnp.float32), col(12.0), col(5.0),
        col(0.0), col(20.0), col(0.1), col(0.0), col(0.0), depth=12,
        n_bins=32, num_classes=2, task="binary", n_rounds=20, sweep=False,
        n_slots=256).as_text()
    blocked = re.findall(r"dot_general[^\n]*: \(tensor<8x\d+x(\d+)x(\w+)>, "
                         r"tensor<8x\d+x(\d+)x\w+>\)", text)
    levels = sorted({int(a) for a, dt, _ in blocked if dt == "bf16"})
    assert levels == [3 * 128, 3 * 256]
    assert [(int(a), int(b)) for a, dt, b in blocked if dt == "f32"] == [
        (2 * 4, 4 * 256)]
