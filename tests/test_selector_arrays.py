"""The selector's labels and evaluation as arrays (PR 27): DataCutter's
(de-)indexing is one lookup (``LabelIndex``) that the host, the planned and
the single-row scoring paths share, against the dict semantics it replaced;
the stock evaluators state their metrics over arrays (``evaluate_parts``),
the true class's rank by counting against a stable sort; and a train holds
the mechanism without a clock: no ``np.vectorize``, the evaluation on the
device, a user's table-only evaluator on the table path."""
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import Column, FeatureBuilder, FeatureTable
from transmogrifai_tpu.evaluators import (
    OpBinaryClassificationEvaluator, OpMultiClassificationEvaluator,
    OpRegressionEvaluator)
from transmogrifai_tpu.evaluators.base import OpEvaluatorBase, evaluates_parts
from transmogrifai_tpu.impl.selector import (
    BinaryClassificationModelSelector, MultiClassificationModelSelector,
    RegressionModelSelector)
from transmogrifai_tpu.impl.selector.model_selector import (
    SelectedModel, prediction_column)
from transmogrifai_tpu.impl.tuning import DataCutter
from transmogrifai_tpu.impl.tuning.splitters import LabelIndex, label_index
from transmogrifai_tpu.models.api import (
    MODEL_REGISTRY, FittedParams, ModelFamily)
from transmogrifai_tpu.observability import trace as obs
from transmogrifai_tpu.types import OPVector, RealNN


# -- the lookup against the dict semantics it replaces ------------------------

class _Echo(ModelFamily):
    """A family whose prediction is the first feature: dense indices in,
    the model's de-indexing out."""
    name = "_Echo"
    supports = frozenset({"multiclass"})

    def default_grid(self, problem):
        return [{}]

    def fit_batch(self, X, y, weights, grid, num_classes):
        raise NotImplementedError

    def predict_batch(self, params, X, num_classes):
        raise NotImplementedError

    def predict_parts(self, fitted, X):
        return {"prediction": X[:, 0]}

    def predict_one(self, fitted, X):
        return {"prediction": np.asarray(X[:, 0])}


GAPS = {0: 0, 2: 1, 3: 2}
WIDE = {-5: 0, 3: 1, 1234567: 2}
LOOKUP_CASES = {
    "identity": ({0: 0, 1: 1, 2: 2}, [0, 1, 2, 2, 0], [0, 1, 2, 1]),
    "gaps": (GAPS, [0, 2, 3, 3, 2, 0], [0, 1, 2, 1]),
    "negative_and_seven_digit_labels": (
        WIDE, [-5, 3, 1234567, 3, -5], [2, 0, 1, 2]),
    "a_label_that_was_not_kept": (
        WIDE, [1, 99, -6, 1234566, 7654321, 3], [1]),
    "float_labels_with_a_fraction": (
        WIDE, [2.7, 3.9, -5.9, -4.2, -0.5, 1234567.0], [0.0, 1.9, 2.2]),
    "an_empty_array": (GAPS, [], []),
    "a_dense_index_with_no_entry": (
        GAPS, [3], [-3, -1, 0, 2, 3, 7, 1e6, -0.5, 2.9]),
}


@pytest.mark.parametrize("case", sorted(LOOKUP_CASES))
def test_the_lookup_is_the_dict_it_replaces_on_every_path(case, monkeypatch):
    mapping, labels, dense = LOOKUP_CASES[case]
    labels = np.asarray(labels, dtype=np.float32)
    dense = np.asarray(dense, dtype=np.float32)
    inverse_dict = {d: o for o, d in mapping.items()}
    want_forward = np.array([mapping.get(int(v), -1) for v in labels],
                            dtype=np.float32)
    want_inverse = np.array([inverse_dict.get(int(v), int(v)) for v in dense],
                            dtype=np.float32)

    idx = label_index(mapping)
    assert idx is label_index(dict(mapping))        # built once per mapping
    got = idx.forward(labels)
    assert got.dtype == np.float32 and got.shape == labels.shape
    np.testing.assert_array_equal(got, want_forward)

    # host, planned (device_columnar) and single-row path of a fitted model
    monkeypatch.setitem(MODEL_REGISTRY, "_Echo", _Echo())
    sm = SelectedModel(FittedParams("_Echo", {}, {}, 3), None, mapping)
    sm.input_features = (
        FeatureBuilder.RealNN("label").extract_field().as_response(),
        FeatureBuilder.OPVector("features").extract_field().as_predictor())
    host = sm._unmap_prediction(dense)
    np.testing.assert_array_equal(host, want_inverse)
    X = dense[:, None]
    tbl = FeatureTable({"features": Column(OPVector, X, None)}, len(dense))
    np.testing.assert_array_equal(
        np.asarray(sm.transform_column(tbl).values).reshape(-1), want_inverse)
    planned, _ = sm.device_columnar({"features": (jnp.asarray(X), None)})
    np.testing.assert_array_equal(np.asarray(planned)[:, 0], want_inverse)
    rows = [sm.transform_row({"features": [float(v)]})["prediction"]
            for v in dense]
    np.testing.assert_array_equal(np.asarray(rows, np.float32), want_inverse)


def test_a_negative_dense_index_is_no_mapping():
    with pytest.raises(ValueError):
        LabelIndex({4: -1})
    assert label_index(None) is None and label_index({}) is None


# -- the evaluators over arrays ------------------------------------------------

def _numpy_multiclass(prob, label, pred, top_ns, thresholds):
    """A plain statement of the multiclass metrics: a stable sort of
    ``-prob`` ranks tied classes by index; thresholds compare in float64; a
    label without a column is an error and has no log loss."""
    prob64 = prob.astype(np.float64)
    C = prob.shape[1]
    order = np.argsort(-prob, axis=1, kind="stable")
    has = (label >= 0) & (label < C)
    out = {"Error": float((pred != label).mean())}
    made = prob64.max(axis=1)[:, None] >= np.asarray(thresholds)[None, :]
    tables = {"correctCounts": {}, "incorrectCounts": {},
              "noPredictionCounts": {}}
    for n in top_ns:
        hit = (order[:, :n] == label[:, None]).any(axis=1) & has
        out[f"TopN_{n}_Accuracy"] = float(hit.mean())
        tables["correctCounts"][n] = (hit[:, None] & made).sum(0).tolist()
        tables["incorrectCounts"][n] = (~hit[:, None] & made).sum(0).tolist()
        tables["noPredictionCounts"][n] = (len(prob)
                                           - made.sum(0)).tolist()
    picked = prob64[np.nonzero(has)[0], label[has]]
    out["LogLoss"] = float(-np.log(np.clip(picked, 1e-15, 1.0)).mean())
    return out, tables


def _tied_block(rows, classes, seed, levels):
    """Probabilities on a coarse grid, so that rows have tied classes (the
    largest among them), labels and predictions over every class."""
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, levels, (rows, classes)).astype(np.float32) + 1.0
    prob = (raw / raw.sum(axis=1, keepdims=True)).astype(np.float32)
    label = rng.integers(0, classes, rows)
    return prob, label, prob.argmax(axis=1)


@pytest.mark.parametrize("rows,classes,seed,levels", [
    (257, 5, 0, 3), (1000, 23, 1, 4), (64, 3, 2, 2), (513, 7, 3, 50)])
def test_rank_by_counting_is_the_stable_sort(rows, classes, seed, levels):
    prob, label, pred = _tied_block(rows, classes, seed, levels)
    if levels < 10:        # the blocks are there for their ties
        assert (np.sort(prob, axis=1)[:, -1] == np.sort(prob, axis=1)[:, -2]
                ).any()
    ev = OpMultiClassificationEvaluator(top_ns=(1, 3))
    got = ev.evaluate_parts(label.astype(np.float32), {
        "prediction": pred.astype(np.float32), "probability": prob})
    want, tables = _numpy_multiclass(prob, label, pred, ev.top_ns,
                                     ev.thresholds)
    for k in ("TopN_1_Accuracy", "TopN_3_Accuracy"):
        assert got[k] == want[k]                    # a count over the rows
    for k in ("Error", "LogLoss"):
        assert got[k] == pytest.approx(want[k], rel=2e-6)
    for k, table in tables.items():
        assert got["ThresholdMetrics"][k] == table
    assert got["ThresholdMetrics"]["thresholds"] == list(ev.thresholds)
    # the public threshold helper is the same statement
    assert ev.threshold_metrics(prob, label) == got["ThresholdMetrics"]


def test_a_threshold_at_a_float32_probability_decides_as_float64_does():
    """Each stock threshold's float32 neighbours as the largest probability:
    ``float32(0.3)`` lies above 0.3 and counts, ``float32(0.7)`` lies below
    0.7 and does not."""
    ev = OpMultiClassificationEvaluator(top_ns=(1,))
    tops = []
    for t in ev.thresholds:
        t32 = np.float32(t)
        tops += [np.nextafter(t32, np.float32(-1)), t32,
                 np.nextafter(t32, np.float32(2))]
    tops = np.clip(np.asarray(tops, np.float32), 0.0, 1.0)
    prob = np.stack([tops, (1 - tops) / 2, (1 - tops) / 2], axis=1)
    prob = prob.astype(np.float32)
    label = np.zeros(len(tops), np.int64)
    got = ev.threshold_metrics(prob, label)
    made = (prob.astype(np.float64).max(axis=1)[:, None]
            >= np.asarray(ev.thresholds)[None, :])
    assert got["noPredictionCounts"][1] == (len(tops)
                                            - made.sum(axis=0)).tolist()
    assert float(np.float32(0.3)) > 0.3 and float(np.float32(0.7)) < 0.7
    assert 0 < sum(got["noPredictionCounts"][1]) < made.size


def test_a_dropped_label_and_a_mapping_with_gaps_by_hand():
    """Labels {0, 2, 3} kept as 0, 1, 2 and a label 5 the cutter dropped.
    In dense space (the selector's evaluation) the log loss of a label-2 row
    reads column 1; indexed by the raw label, as before PR 27, it read
    column 2 and a label-3 row a clamped one."""
    dense = label_index({0: 0, 2: 1, 3: 2}).forward(
        np.array([0, 2, 3, 5, 2], np.float32))
    np.testing.assert_array_equal(dense, [0, 1, 2, -1, 1])
    prob = np.array([[0.7, 0.2, 0.1],      # label 0, predicted 0
                     [0.1, 0.6, 0.3],      # label 1, predicted 1
                     [0.5, 0.3, 0.2],      # label 2, predicted 0: rank 2
                     [0.2, 0.5, 0.3],      # dropped label: an error
                     [0.4, 0.4, 0.2]],     # label 1 ties with class 0: rank 1
                    np.float32)
    ev = OpMultiClassificationEvaluator(top_ns=(1, 3),
                                        thresholds=(0.0, 0.5, 0.65))
    got = ev.evaluate_parts(dense, {
        "prediction": prob.argmax(axis=1).astype(np.float32),
        "probability": prob})
    assert got["Error"] == pytest.approx(3 / 5)
    assert got["Precision"] == pytest.approx(1 / 15 + 1 / 5)
    assert got["Recall"] == pytest.approx(2 / 5)
    assert got["F1"] == pytest.approx(3 / 10)
    assert got["TopN_1_Accuracy"] == 2 / 5
    assert got["TopN_3_Accuracy"] == 4 / 5
    assert got["LogLoss"] == pytest.approx(
        -np.log(np.array([0.7, 0.6, 0.2, 0.4], np.float32)).mean(), rel=1e-6)
    tm = got["ThresholdMetrics"]
    assert tm["correctCounts"] == {1: [2, 2, 1], 3: [4, 3, 1]}
    assert tm["incorrectCounts"] == {1: [3, 2, 0], 3: [1, 1, 0]}
    assert tm["noPredictionCounts"] == {1: [0, 1, 4], 3: [0, 1, 4]}


def _scored_table(label, parts):
    return FeatureTable({"label": Column(RealNN, label, None),
                         "pred": prediction_column(parts)}, len(label))


def _stock_case(kind):
    rng = np.random.default_rng(11)
    n = 300
    if kind == "multiclass":
        prob, label, pred = _tied_block(n, 4, 5, 6)
        return (OpMultiClassificationEvaluator(), label.astype(np.float32),
                {"prediction": pred.astype(np.float32), "probability": prob,
                 "rawPrediction": np.log(prob)})
    if kind == "binary":
        p1 = rng.random(n).astype(np.float32)
        label = (rng.random(n) < p1).astype(np.float32)
        return (OpBinaryClassificationEvaluator(), label,
                {"prediction": (p1 > 0.5).astype(np.float32),
                 "probability": np.stack([1 - p1, p1], axis=1)})
    label = rng.standard_normal(n).astype(np.float32)
    return (OpRegressionEvaluator(), label, {
        "prediction": label + 0.3 * rng.standard_normal(n).astype(np.float32)})


@pytest.mark.parametrize("kind", ["binary", "multiclass", "regression"])
def test_evaluate_all_is_evaluate_parts_of_its_own_extraction(kind):
    ev, label, parts = _stock_case(kind)
    assert evaluates_parts(ev)
    ev.set_label_col("label").set_prediction_col("pred")
    table = _scored_table(label, parts)
    whole = ev.evaluate_all(table)
    assert whole == ev.evaluate_parts(*ev._extract(table))
    # padded to a bucket by the caller (as the selector does, on the
    # device): the same numbers
    pad = 512 - len(label)
    padded = ev.evaluate_parts(
        jnp.asarray(np.pad(label, (0, pad))),
        {k: jnp.asarray(np.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1),
                               constant_values=0.25))
         for k, v in parts.items()}, np.arange(512) < len(label))
    assert set(padded) == set(whole)
    for k, v in whole.items():
        if isinstance(v, float):
            assert padded[k] == pytest.approx(v, rel=1e-5, abs=1e-6), k
    assert ev.evaluate(table) == whole[ev.default_metric]


def test_regression_metrics_are_the_plain_ones():
    ev, label, parts = _stock_case("regression")
    got = ev.evaluate_parts(label, parts)
    err = parts["prediction"].astype(np.float64) - label
    assert got["RootMeanSquaredError"] == pytest.approx(
        np.sqrt((err ** 2).mean()), rel=1e-5)
    assert got["MeanAbsoluteError"] == pytest.approx(np.abs(err).mean(),
                                                     rel=1e-5)
    assert got["R2"] == pytest.approx(
        1 - (err ** 2).sum() / ((label - label.mean()) ** 2).sum(), rel=1e-5)


# -- the mechanism in a train, without a clock ---------------------------------

class _TableOnlyEvaluator(OpEvaluatorBase):
    """A user's evaluator: all it defines is ``evaluate_all(table)``."""
    default_metric = "F1"

    def evaluate_all(self, table):
        label, parts = self._extract(table)
        pred = parts["prediction"]
        classes = np.unique(np.concatenate([label, pred]))
        f1 = 0.0
        for c in classes:
            tp = float(((pred == c) & (label == c)).sum())
            prec = tp / max(float((pred == c).sum()), 1.0)
            rec = tp / max(float((label == c).sum()), 1.0)
            if prec + rec > 0:
                f1 += (label == c).mean() * 2 * prec * rec / (prec + rec)
        return {"Error": float((pred != label).mean()), "F1": float(f1)}


def _cut_table(n=900, seed=7):
    """Labels {0, 2, 3} and a rare 7 that the cutter drops: a mapping that
    is not the identity."""
    rng = np.random.default_rng(seed)
    cls = rng.choice(4, n, p=[0.4, 0.3, 0.28, 0.02])
    X = (np.eye(4)[cls] * 2.0 + rng.standard_normal((n, 4))).astype(
        np.float32)
    y = np.array([0, 2, 3, 7], np.float32)[cls]
    return FeatureTable({"label": Column(RealNN, y, None),
                         "features": Column(OPVector, X, None)}, n), y


def _train_traced(selector, table):
    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    feats = FeatureBuilder.OPVector("features").extract_field().as_predictor()
    selector.set_input(label, feats)
    obs.enable_tracing(True)
    obs.tracer().clear()
    try:
        model = selector.fit(table)
        spans = {s.name: s.attrs for s in obs.tracer().finished()}
    finally:
        obs.enable_tracing(False)
    return model, spans


LR_POINT = [("OpLogisticRegression",
             [{"regParam": 0.01, "elasticNetParam": 0.0}])]


_CUT_TRAIN = []


@pytest.fixture
def cut_train():
    """The train every mechanism test reads, made once (inside the first
    test that asks: conftest's leak check wants a clean entry): stock
    evaluator, a cutter that drops a label, ``np.vectorize`` forbidden."""
    if not _CUT_TRAIN:
        _CUT_TRAIN.append(_make_cut_train())
    return _CUT_TRAIN[0]


def _make_cut_train():
    table, y = _cut_table()
    patch = pytest.MonkeyPatch()

    real = np.vectorize

    def forbidden(*a, **k):
        # jax builds one over DEVICES while it compiles; the program may not
        if "transmogrifai_tpu" in sys._getframe(1).f_code.co_filename:
            raise AssertionError("np.vectorize: a Python call a row")
        return real(*a, **k)

    patch.setattr(np, "vectorize", forbidden)
    try:
        sel = MultiClassificationModelSelector.with_cross_validation(
            splitter=DataCutter(min_label_fraction=0.05, seed=42),
            models=LR_POINT)
        model, spans = _train_traced(sel, table)
        scored = model.transform_column(table)       # the closing transform
    finally:
        patch.undo()
    return table, y, model, spans, scored


def test_a_cut_train_runs_without_a_python_call_a_row(cut_train):
    table, y, model, spans, scored = cut_train
    assert model.label_mapping == {0: 0, 2: 1, 3: 2}
    assert spans["selector.prepare"]["labelMap"] == "lookup"
    ev = spans["selector.evaluate"]
    assert (ev["labelMap"], ev["evalPath"]) == ("lookup", "device")
    one_prediction_column = ev["rows"] * 4
    assert 0 < ev["hostBytes"] < one_prediction_column
    # a user reads original labels in the scored table
    pred = np.asarray(scored.values)[:, 0]
    assert set(np.unique(pred)) <= {0.0, 2.0, 3.0}
    assert (pred == y).mean() > 0.8


def test_a_cut_trains_summary_is_in_dense_space(cut_train):
    """Part 4: the dropped label's rows are errors, and LogLoss reads each
    row's own column (in raw-label space a label-3 row has none)."""
    table, y, model, _, scored = cut_train
    s = model.summary
    train_idx, test_idx = DataCutter(seed=42).split(len(y))
    for idx, got in ((train_idx, s.train_evaluation),
                     (test_idx, s.holdout_evaluation)):
        vals = np.asarray(scored.values)[idx]
        prob = vals[:, [list(scored.metadata["keys"]).index(
            f"probability_{i}") for i in range(3)]]
        dense = label_index(model.label_mapping).forward(y[idx]).astype(int)
        want, _ = _numpy_multiclass(prob, dense, prob.argmax(axis=1),
                                    (1, 3), ())
        assert (dense < 0).any() or idx is test_idx
        for k in ("Error", "LogLoss", "TopN_1_Accuracy", "TopN_3_Accuracy"):
            assert got[k] == pytest.approx(want[k], rel=1e-5), k
    assert set(s.train_evaluation) == {
        "Error", "Precision", "Recall", "F1", "LogLoss", "TopN_1_Accuracy",
        "TopN_3_Accuracy"}


def test_a_table_only_evaluator_keeps_the_table_path(cut_train):
    table, y, model, _, _ = cut_train
    ev = _TableOnlyEvaluator()
    assert not evaluates_parts(ev)
    sel = MultiClassificationModelSelector.with_cross_validation(
        splitter=DataCutter(min_label_fraction=0.05, seed=42),
        models=LR_POINT, evaluator=ev)
    user_model, spans = _train_traced(sel, table)
    attrs = spans["selector.evaluate"]
    assert (attrs["labelMap"], attrs["evalPath"]) == ("lookup", "table")
    assert attrs["hostBytes"] > attrs["rows"] * 4
    for mine, stock in ((user_model.summary.train_evaluation,
                         model.summary.train_evaluation),
                        (user_model.summary.holdout_evaluation,
                         model.summary.holdout_evaluation)):
        assert set(mine) == {"Error", "F1"}
        for k in mine:
            assert mine[k] == pytest.approx(stock[k], rel=1e-5), k


class _OverridesEvaluateAll(OpMultiClassificationEvaluator):
    def evaluate_all(self, table):
        return dict(super().evaluate_all(table), Mine=1.0)


def _problem(kind):
    rng = np.random.default_rng(3)
    n = 400
    X = rng.standard_normal((n, 3)).astype(np.float32)
    if kind == "binary":
        y = (X[:, 0] + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
        return (BinaryClassificationModelSelector, y, X,
                OpBinaryClassificationEvaluator, LR_POINT)
    if kind == "multiclass":
        y = np.argmax(X + 0.3 * rng.standard_normal((n, 3)),
                      axis=1).astype(np.float32)
        return (MultiClassificationModelSelector, y, X,
                OpMultiClassificationEvaluator, LR_POINT)
    y = (X @ np.array([1.0, -2.0, 0.5], np.float32)
         + 0.1 * rng.standard_normal(n)).astype(np.float32)
    return (RegressionModelSelector, y, X, OpRegressionEvaluator,
            [("OpLinearRegression", [{"regParam": 0.01,
                                      "elasticNetParam": 0.0}])])


@pytest.mark.parametrize("kind", ["binary", "multiclass", "regression"])
def test_device_and_table_evaluation_agree_for_every_problem_kind(kind):
    """One block for the three selectors: the stock evaluator goes the
    device path; the same evaluator with ``evaluate_all`` overridden is
    handed tables; the summaries agree to float32 rounding."""
    factory, y, X, stock, models = _problem(kind)
    table = FeatureTable({"label": Column(RealNN, y, None),
                          "features": Column(OPVector, X, None)}, len(y))

    class Overriding(stock):
        def evaluate_all(self, tbl):
            return super().evaluate_all(tbl)

    summaries = {}
    for name, ev in (("device", stock()), ("table", Overriding())):
        sel = factory.with_cross_validation(models=models, evaluator=ev)
        model, spans = _train_traced(sel, table)
        assert spans["selector.evaluate"]["evalPath"] == name
        assert spans["selector.evaluate"]["labelMap"] == (
            "lookup" if kind == "multiclass" else "none")
        summaries[name] = model.summary
    assert not evaluates_parts(_OverridesEvaluateAll())
    for part in ("train_evaluation", "holdout_evaluation"):
        dev, tab = (getattr(summaries[k], part) for k in ("device", "table"))
        assert set(dev) == set(tab) and dev
        for k in dev:
            assert dev[k] == pytest.approx(tab[k], rel=2e-5, abs=2e-6), k


def test_under_a_mesh_the_evaluation_keeps_the_refits_sharding():
    """Rows sharded over 'data' as the refit placed them: the device path,
    and the one chip's numbers."""
    from transmogrifai_tpu.parallel.mesh import MeshSpec, make_mesh
    factory, y, X, stock, models = _problem("multiclass")
    table = FeatureTable({"label": Column(RealNN, y, None),
                          "features": Column(OPVector, X, None)}, len(y))
    summaries = []
    for mesh in (None, make_mesh(MeshSpec(data=4, model=2))):
        sel = factory.with_cross_validation(models=models)
        if mesh is not None:
            sel.set_mesh(mesh)
        model, spans = _train_traced(sel, table)
        assert spans["selector.evaluate"]["evalPath"] == "device"
        summaries.append(model.summary)
    one, sharded = summaries
    for part in ("train_evaluation", "holdout_evaluation"):
        a, b = getattr(one, part), getattr(sharded, part)
        assert set(a) == set(b) and a
        for k in a:
            assert a[k] == pytest.approx(b[k], rel=1e-4, abs=1e-5), k
