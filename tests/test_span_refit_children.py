"""What a TREE winner's train says about its refit and its predicts
(PR 39): ``refit.grow`` under ``selector.refit`` where boosted trees win
(the regrow on the split-search sample is all of that refit; a forest's
refit is one program, growth and exact leaf pass together, and has no child,
nor has a linear winner's), the descent's shape on ``evaluate.predict`` and
``predict.parts``; and with tracing off none of it exists."""
import gc

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder, observability
from transmogrifai_tpu.impl.selector import factories
from transmogrifai_tpu.observability import trace as ot
from transmogrifai_tpu.workflow import OpWorkflow

ROWS = 900
# hyperparameters no other test uses (tests/test_span_layers.py says why)
MODELS = {
    "forest": [("OpRandomForestClassifier", [
        {"maxDepth": 4, "numTrees": 5, "minInfoGain": 0.00139}])],
    "boosted": [("OpGBTClassifier", [
        {"maxDepth": 3, "maxIter": 4, "minInfoGain": 0.00139}])],
    "linear": [("OpLogisticRegression", [
        {"regParam": 0.0139, "elasticNetParam": 0.0}])],
}
DESCENT = {"trees", "depth", "features", "treeChunks", "blockRows",
           "laneChunk"}


def _df(seed=13):
    rng = np.random.RandomState(seed)
    x1, x2, x3 = rng.randn(ROWS), rng.randn(ROWS), rng.randn(ROWS)
    y = ((np.abs(x1) < 0.8) & (x2 > -0.5)).astype(float)
    return pd.DataFrame({"x1": x1, "x2": x2, "x3": x3, "y": y})


def _workflow(df, winner):
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
             for c in ("x1", "x2", "x3")]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (factories.BinaryClassificationModelSelector
            .with_cross_validation(models=MODELS[winner])
            .set_input(label, checked).get_output())
    return OpWorkflow().set_input_dataset(df).set_result_features(pred)


@pytest.fixture(scope="module", params=sorted(MODELS))
def train(request):
    gc.collect()
    ot.reset()
    ot.enable_tracing(True)
    try:
        _workflow(_df(), request.param).train()
        spans = ot.tracer().finished()
    finally:
        observability.reset()
    return request.param, spans


def _children(spans, parent_name):
    (parent,) = [s for s in spans if s.name == parent_name]
    kids = [s for s in spans if s.parent_id == parent.span_id]
    return parent, sorted(kids, key=lambda s: s.ts_ns)


def test_the_refit_has_a_child_a_program(train):
    winner, spans = train
    refit, kids = _children(spans, "selector.refit")
    want = {"forest": [], "boosted": ["refit.grow"], "linear": []}[winner]
    assert [k.name for k in kids] == want
    for k in kids:
        assert refit.ts_ns <= k.ts_ns
        assert k.ts_ns + k.dur_ns <= refit.ts_ns + refit.dur_ns


def test_the_children_say_what_they_worked_on(train):
    winner, spans = train
    refit, kids = _children(spans, "selector.refit")
    if winner != "boosted":
        return
    (grow,) = kids
    hyper = MODELS[winner][0][1][0]
    assert grow.attrs["family"] == refit.attrs["family"]
    assert grow.attrs["depth"] == hyper["maxDepth"]
    assert grow.attrs["trees"] == hyper.get("numTrees",
                                            hyper.get("maxIter"))
    assert grow.attrs["slots"] == 0          # a complete heap at this depth
    # the rows kept, padded to the refit's bucket: all of them grown on
    assert refit.attrs["rows"] <= grow.attrs["sampleRows"] <= 65536
    # one tree a round, laid on one tree lane (PR 43: it was 32), and the
    # refit's own span says the same of its family's program
    for s in (grow, refit):
        assert (s.attrs["treeLanes"], s.attrs["treeLanesPadded"]) == (1, 1)


def test_a_boosted_sweep_says_how_full_its_tree_lanes_are(train):
    """``treeLanes``: the trees a boosting round grows side by side, every
    configuration of every fold; ``treeLanesPadded``: the lanes the node
    histogram lays out for them, by the histogram engine's own rule. No
    other family's sweep carries either."""
    from transmogrifai_tpu.histeng.kernels import tree_lane_shape
    winner, spans = train
    (sweep,) = [s for s in spans if s.name == "sweep.family"]
    if winner != "boosted":
        assert not {"treeLanes", "treeLanesPadded"} & set(sweep.attrs)
        return
    lanes = sweep.attrs["configs"] * sweep.attrs["folds"]
    assert sweep.attrs["treeLanes"] == lanes == sweep.attrs["lanes"] > 1
    assert sweep.attrs["treeLanesPadded"] == tree_lane_shape(lanes)[0]


def test_the_predicts_say_what_they_descend(train):
    winner, spans = train
    launches = [s for s in spans
                if s.name in ("evaluate.predict", "predict.parts")]
    assert [s.name for s in sorted(launches, key=lambda s: s.ts_ns)] == [
        "evaluate.predict", "evaluate.predict", "predict.parts"]
    for s in launches:
        if winner == "linear":
            assert not DESCENT & set(s.attrs)
            continue
        assert DESCENT <= set(s.attrs)
        hyper = MODELS[winner][0][1][0]
        assert s.attrs["depth"] == hyper["maxDepth"]
        assert s.attrs["trees"] == hyper.get("numTrees",
                                             hyper.get("maxIter"))
        assert s.attrs["treeChunks"] == 1 and s.attrs["features"] == 3
        # a complete heap's kernel: 128 rows a block, a level's lanes whole
        # (the chain kernels' blocks: tests/test_deep_trees.py)
        assert (s.attrs["blockRows"], s.attrs["laneChunk"]) == (128, 0)
    # the rows a predict is asked for: a split's, and the whole table's
    assert sorted(s.attrs["rows"] for s in launches) == [
        ROWS // 10, ROWS - ROWS // 10, ROWS]


@pytest.mark.parametrize("winner", ["forest", "boosted"])
def test_tracing_off_makes_no_span(monkeypatch, winner):
    made = []
    real_start = ot.Tracer.start
    monkeypatch.setattr(ot.Tracer, "start", lambda self, name, *a, **kw: (
        made.append(name), real_start(self, name, *a, **kw))[1])
    assert not ot.tracing_enabled()
    _workflow(_df(), winner).train()
    assert made == [] and not ot.tracer().finished()
