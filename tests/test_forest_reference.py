"""``benchmark/reference_forest.py`` against the program on seeded random
forests, in both layouts the growers have (slot chains past depth 8,
complete heaps up to it): the numpy descent gives the program's scores, the
leaf recomputation the refit's leaf values, the controls are told apart,
and the rows a refit's trees are grown on follow the program's rule."""
import ast
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_forest as rf
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.api import MODEL_REGISTRY, FittedParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = "OpRandomForestClassifier"
N, D, PADS = 6000, 9, 144


def _rows(seed=5):
    rng = np.random.default_rng(seed)
    X = rng.lognormal(0.0, 0.4, size=(N, D)).astype(np.float32)
    bump = np.exp(-0.5 * (np.log(X[:, 0]) / 0.2) ** 2)
    z = -1.5 + 3.0 * bump + 2.0 * bump * (X[:, 1] > 1.0) + 0.3 * X[:, 2]
    y = (rng.random(N) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    return X, y


@pytest.fixture(scope="module", params=[12, 5], ids=["chain", "heap"])
def fitted(request):
    """A refit as the selector makes it: rows padded with weight zero."""
    X, y = _rows()
    hyper = {"maxDepth": request.param, "minInstancesPerNode": 10,
             "minInfoGain": 0.001, "numTrees": 6, "subsamplingRate": 1.0}
    family = MODEL_REGISTRY[FAMILY]
    Xp = np.concatenate([X, np.zeros((PADS, D), np.float32)])
    yp = np.concatenate([y, np.zeros(PADS, np.float32)])
    W = np.concatenate([np.ones(N), np.zeros(PADS)]).astype(np.float32)[None]
    batched = family.fit_batch(jnp.asarray(Xp), jnp.asarray(yp),
                               jnp.asarray(W), family.grid_to_arrays([hyper]),
                               2)
    params = family.select_params(batched, 0)
    model = FittedParams(family=FAMILY, params=params, hyper=hyper,
                         num_classes=2)
    prob = family.predict_one(model, jnp.asarray(X))["probability"][:, 1]
    return X, y, params, np.asarray(prob), hyper


def test_the_layout_is_the_one_the_depth_asks_for(fitted):
    _, _, params, _, hyper = fitted
    assert ("base_lv" in params) == (hyper["maxDepth"] > 8)
    assert rf.tree_depth(params) == hyper["maxDepth"]
    assert rf.n_trees(params) == hyper["numTrees"]


def test_the_numpy_descent_gives_the_programs_score(fitted):
    X, _, params, prob, _ = fitted
    got = rf.class1_score(FAMILY, params, X)
    assert np.abs(got - prob).max() < 1e-6
    # blocks of rows change nothing
    assert np.array_equal(rf.forest_score(params, X, block=700), got)


def test_thresholds_in_bfloat16_move_the_score(fitted):
    X, _, params, prob, _ = fitted
    low = rf.class1_score(FAMILY, params, X, "bf16")
    assert np.abs(low - prob).max() > 1e-2


def test_the_leaf_recomputation_gives_the_refits_leaves(fitted):
    X, y, params, _, hyper = fitted
    sums = rf.leaf_class_sums(params, X, y, np.ones(N))
    assert sums.sum() == pytest.approx(N * hyper["numTrees"])
    diff, leaves = rf.refit_leaves(params, sums, hyper["minInstancesPerNode"])
    assert diff < 1e-6 and leaves >= 4 * hyper["numTrees"]
    # the same in blocks of rows
    again = rf.leaf_class_sums(params, X, y, np.ones(N), block=999)
    np.testing.assert_allclose(again, sums)


def test_leaves_from_a_sample_of_the_rows_are_told_apart(fitted):
    X, y, params, _, hyper = fitted
    some = np.arange(0, N, 4)
    sums = rf.leaf_class_sums(params, X[some], y[some], np.ones(len(some)))
    diff, _ = rf.refit_leaves(params, sums, hyper["minInstancesPerNode"])
    assert diff > 1e-2
    # the control's forest: the program's splits with the sample's leaves
    control = rf.with_leaves_from(params, sums)
    full = rf.leaf_class_sums(params, X, y, np.ones(N))
    assert rf.refit_leaves(control, full, 10)[0] > 1e-2
    assert np.abs(rf.forest_score(control, X)
                  - rf.forest_score(params, X)).max() > 1e-3


def test_a_shuffled_label_gives_other_leaves(fitted):
    X, y, params, _, hyper = fitted
    perm = np.random.default_rng(1).permutation(N)
    sums = rf.leaf_class_sums(params, X, y[perm], np.ones(N))
    assert rf.refit_leaves(params, sums, 10)[0] > 5e-2


def test_the_root_splits_are_near_the_best_of_their_column(fitted):
    X, y, params, _, _ = fitted
    worst, mean, roots = rf.root_split_shortfall(params, X, y)
    assert 0.0 <= mean <= worst < 0.5 and roots == rf.n_trees(params)
    # the same trees with every root's bin moved to the table's last edge
    key = "bins_lv" if "base_lv" in params else "bins"
    moved = dict(params, **{key: np.array(params[key])})
    if key == "bins_lv":
        moved[key][:, 0, 0] = 30
    else:
        moved[key][:, 0] = 30
    assert rf.root_split_shortfall(moved, X, y)[1] > max(10 * mean, 0.2)


@pytest.fixture(scope="module", params=[12, 4], ids=["chain", "heap"])
def boosted(request):
    """A boosted refit as the selector makes it."""
    X, y = _rows()
    hyper = {"maxDepth": request.param, "minInstancesPerNode": 10,
             "minInfoGain": 0.001, "maxIter": 5, "stepSize": 0.1}
    family = MODEL_REGISTRY["OpGBTClassifier"]
    Xp = np.concatenate([X, np.zeros((PADS, D), np.float32)])
    yp = np.concatenate([y, np.zeros(PADS, np.float32)])
    W = np.concatenate([np.ones(N), np.zeros(PADS)]).astype(np.float32)[None]
    params = family.select_params(family.fit_batch(
        jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(W),
        family.grid_to_arrays([hyper]), 2), 0)
    model = FittedParams(family="OpGBTClassifier", params=params,
                         hyper=hyper, num_classes=2)
    prob = family.predict_one(model, jnp.asarray(X))["probability"][:, 1]
    return X, y, params, np.asarray(prob), hyper


def test_a_boosted_score_is_the_margins_sigmoid(boosted):
    X, _, params, prob, hyper = boosted
    assert rf.tree_depth(params) == hyper["maxDepth"]
    got = rf.class1_score("OpGBTClassifier", params, X)
    assert np.abs(got - prob).max() < 1e-6
    assert np.abs(rf.class1_score("OpGBTClassifier", params, X, "bf16")
                  - prob).max() > 1e-3


def test_the_rounds_retrained_give_the_programs_leaves(boosted):
    X, y, params, _, hyper = boosted
    grown = rf.grown_rows(N, N + PADS, 65536)
    assert len(grown) == N
    got = rf.boosted_rounds(params, X[grown], y[grown],
                            hyper["minInstancesPerNode"])
    diff = got["leaf_max_abs_diff"]
    assert diff < 1e-5 and got["leaves_compared"] >= 4 * hyper["maxIter"]
    assert got["roots_compared"] == hyper["maxIter"]
    # a running score that never moves: the later rounds' leaves are others
    frozen = rf.boosted_rounds(params, X[grown], y[grown],
                               hyper["minInstancesPerNode"], frozen=True)
    assert frozen["leaf_max_abs_diff"] > max(1e3 * diff, 1e-2)
    # other rows than the trees were grown on
    some = np.arange(0, N, 2)
    assert rf.boosted_rounds(params, X[some], y[some],
                             10)["leaf_max_abs_diff"] > 1e-2


def test_every_split_is_the_best_its_node_offers(boosted):
    """Every tree sees every column and every row, so at EVERY node that
    splits the stated split is the best second-order gain of the rows the
    reference routes there, root or not."""
    X, y, params, _, hyper = boosted
    got = rf.boosted_rounds(params, X, y, hyper["minInstancesPerNode"])
    assert got["splits_compared"] > 3 * got["roots_compared"]
    assert 0.0 <= got["split_shortfall_mean"] <= got["split_shortfall_max"]
    # the histograms' rounding: a few small nodes take a near-best split
    assert got["split_shortfall_max"] < 0.05
    assert got["split_shortfall_mean"] < 1e-3
    assert got["splits_short"] < 0.05 * got["splits_compared"]
    assert got["root_shortfall_max"] < 0.02
    # the roots four bins off: the root's number and the whole's both say so
    roots = rf.boosted_rounds(rf.with_roots_moved(params, 4), X, y, 10)
    assert roots["root_shortfall_max"] > 0.2
    assert roots["split_shortfall_max"] >= roots["root_shortfall_max"]
    # a level BELOW the root four bins off: the first round's root is as it
    # was, and the whole says what the roots alone would not
    deep = rf.boosted_rounds(rf.with_splits_moved(params, 4, 2), X, y, 10)
    assert deep["split_shortfall_max"] > 0.5 > deep["root_shortfall_max"]
    assert deep["split_shortfall_mean"] > 0.1
    assert deep["splits_short"] > 10 * max(got["splits_short"], 1)


def test_a_candidate_split_leaves_rows_on_both_sides():
    """One node of eight rows in two columns: the best split by gain leaves
    one row alone; with ``child_rows`` 2 it is no candidate and the stated
    split is held to the best of those that are."""
    codes = np.array([[0, 0], [1, 0], [1, 1], [1, 1],
                      [2, 1], [2, 2], [2, 2], [3, 2]])
    g = np.array([-5.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, -1.0])
    h = np.ones(8)
    slot = np.zeros(8, dtype=np.int64)
    lone = rf.node_split_shortfalls(codes, slot, g, h, np.array([0]),
                                    np.array([0]), 5, 0.0, 1.0)
    assert lone.tolist() == [0.0]           # the lone row's split is best
    held = rf.node_split_shortfalls(codes, slot, g, h, np.array([0]),
                                    np.array([0]), 5, 0.0, 2.0)
    assert held.tolist() == [1.0]           # ... and no candidate at 2 rows
    other = rf.node_split_shortfalls(codes, slot, g, h, np.array([1]),
                                     np.array([0]), 5, 0.0, 2.0)
    assert 0.0 <= other[0] < 1.0
    # a slot that does not split is not compared
    assert len(rf.node_split_shortfalls(codes, slot, g, h, np.array([0]),
                                        np.array([4]), 5, 0.0, 1.0)) == 0


def _edges_are_the_samples_quantiles(X, params):
    rebuilt = rf.sample_edges(X, N, N + PADS, 65536)
    assert rebuilt.shape == rf.edges_of(params).shape
    assert rf.edges_rel_diff(params, rebuilt) < 1e-5
    # the quantiles of the rows alone, without the pad's zeros: another table
    assert rf.edges_rel_diff(params, rf.sample_edges(X, N, 0, 0)) > 1e-3


def test_a_forests_edges_are_the_samples_quantiles_pad_rows_included(fitted):
    _edges_are_the_samples_quantiles(fitted[0], fitted[2])


def test_a_boosted_fits_edges_are_the_samples_quantiles(boosted):
    _edges_are_the_samples_quantiles(boosted[0], boosted[2])


@pytest.mark.parametrize("fitted_n, padded, sample", [
    (6000, 6144, 65536), (100000, 102400, 65536), (70000, 131072, 4096)])
def test_the_grown_rows_are_the_programs_sample_of_the_padded_matrix(
        fitted_n, padded, sample):
    want = trees._sample_rows(padded, sample)
    want = want[want < fitted_n]
    np.testing.assert_array_equal(
        rf.grown_rows(fitted_n, padded, sample), want)


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "reference_forest.py")
    tree = ast.parse(open(path).read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "a relative import"
            names.add(node.module.split(".")[0])
    assert names <= {"__future__", "typing", "numpy"}
