"""The spans inside the layers (PR 24): the model selector, the scoring
plan, the one-hot stages and the roots. Each opens and closes once per
operation with its attributes; spans of one operation share a root id; the
wait for the sweep is no child of a dispatch; with tracing off not one
``Span`` is made; results are bit-equal either way; and a profiler reads
the tracer's clock without forcing the eager path."""
import collections

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.impl.selector.model_selector import SelectedModel
from transmogrifai_tpu.observability import metrics as om, trace as ot
from transmogrifai_tpu.workflow import OpWorkflow

# hyperparameters no other test uses: the process-wide fused-program cache
# is keyed without shapes, so a grid shared with another test of the same
# worker could hand this one a program built for another table
MODELS = [("OpLogisticRegression", [{"regParam": 0.0123,
                                     "elasticNetParam": 0.0}]),
          ("OpRandomForestClassifier", [{"maxDepth": 3, "numTrees": 4,
                                         "minInfoGain": 0.00123}]),
          ("OpLinearSVC", [{"regParam": 0.0123}])]
ROWS = 600


def _df(seed=11):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.randn(ROWS), rng.randn(ROWS)
    c1 = rng.choice(["a", "b", "c", "d"], size=ROWS, p=[.4, .3, .2, .1])
    c2 = rng.choice(["u", "v"], size=ROWS)
    y = ((x1 + 0.5 * x2 + (c1 == "a") - (c2 == "u")) > 0).astype(float)
    return pd.DataFrame({"x1": x1, "x2": x2, "c1": c1, "c2": c2, "y": y})


def _workflow(df):
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real("x1").extract_field().as_predictor(),
             FeatureBuilder.Real("x2").extract_field().as_predictor(),
             FeatureBuilder.PickList("c1").extract_field().as_predictor(),
             FeatureBuilder.PickList("c2").extract_field().as_predictor()]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        models=MODELS).set_input(label, checked).get_output())
    return OpWorkflow().set_input_dataset(df).set_result_features(pred), pred


def _run(trace: bool):
    """One train and one score; (spans, Tracer.start calls, scores, fitted
    parameters)."""
    ot.reset()
    om.reset()
    ot.enable_tracing(trace)
    om.enable_metrics(trace)
    starts = []
    real_start = ot.Tracer.start

    def counting(self, name, *a, **kw):
        starts.append(name)
        return real_start(self, name, *a, **kw)
    ot.Tracer.start = counting
    try:
        df = _df()
        wf, pred = _workflow(df)
        model = wf.train()
        scored = model.score(df=df)
    finally:
        ot.Tracer.start = real_start
    fitted = next(s for s in model.stages
                  if isinstance(s, SelectedModel)).fitted
    out = (ot.tracer().finished(), starts,
           np.asarray(scored[pred.name].values),
           {k: np.asarray(v) for k, v in fitted.params.items()})
    ot.reset()
    om.reset()
    return out


@pytest.fixture(scope="module")
def runs():
    return {"on": _run(True), "off": _run(False)}


@pytest.fixture()
def spans(runs):
    return runs["on"][0]


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _root(spans, name):
    (root,) = _named(spans, name)
    return root, [s for s in spans if s.root_id == root.span_id]


ONCE_A_TRAIN = {
    "selector.prepare": (),
    "sweep.collect": ("families",),
    "selector.refit": ("family", "attempts"),
    "selector.evaluate": ("rows",),
    "onehot.concat": ("bytes",),
}


@pytest.mark.parametrize("name", sorted(ONCE_A_TRAIN))
def test_once_a_train_with_its_attrs(spans, name):
    _, mine = _root(spans, "workflow.train")
    (s,) = _named(mine, name)
    assert s.dur_ns is not None and s.dur_ns >= 0
    assert set(ONCE_A_TRAIN[name]) <= set(s.attrs)


def test_the_selectors_spans_say_what_happened(spans):
    _, mine = _root(spans, "workflow.train")
    (collect,) = _named(mine, "sweep.collect")
    assert collect.attrs["families"] == len(MODELS)
    (refit,) = _named(mine, "selector.refit")
    assert refit.attrs["attempts"] == 1
    assert refit.attrs["family"] in {m for m, _ in MODELS}
    (ev,) = _named(mine, "selector.evaluate")
    assert ev.attrs["rows"] == ROWS
    # in order, none inside another, all under the selector's fit
    (fit,) = [s for s in _named(mine, "stage.fit")
              if s.attrs["stage"] == "ModelSelector"]
    chain = [_named(mine, n)[0] for n in (
        "selector.prepare", "sweep.collect", "selector.refit",
        "selector.evaluate")]
    assert all(s.parent_id == fit.span_id for s in chain)
    for a, b in zip(chain, chain[1:]):
        assert a.ts_ns + a.dur_ns <= b.ts_ns


def test_families_are_numbered_in_dispatch_order(spans):
    _, mine = _root(spans, "workflow.train")
    fams = sorted(_named(mine, "sweep.family"), key=lambda s: s.ts_ns)
    assert [s.events for s in fams] == [[]] * len(MODELS)   # none quarantined
    assert [s.attrs["family"] for s in fams] == [m for m, _ in MODELS]
    assert [s.attrs["order"] for s in fams] == list(range(len(MODELS)))
    assert all(s.attrs["programs"] == 1 for s in fams)


def test_the_wait_is_no_child_of_a_dispatch(spans):
    by_id = {s.span_id: s for s in spans}
    for s in _named(spans, "sweep.collect") + _named(spans, "plan.collect"):
        at = s
        while at.parent_id is not None:
            at = by_id[at.parent_id]
            assert at.name not in ("sweep.family", "plan.segment")


def test_one_hot_spans_one_per_column(spans):
    _, mine = _root(spans, "workflow.train")
    for name in ("onehot.count", "onehot.encode", "onehot.expand"):
        got = _named(mine, name)
        assert sorted(s.attrs["column"] for s in got) == ["c1", "c2"], name
    counts = {s.attrs["column"]: s for s in _named(mine, "onehot.count")}
    # the frame's equal one-letter strings reach the pivot as one object
    # each: rows were grouped by object, four objects met for four levels
    assert counts["c1"].attrs == {"column": "c1", "rows": ROWS, "levels": 4,
                                  "path": "hashed", "objects": 4}
    assert (counts["c2"].attrs["levels"], counts["c2"].attrs["objects"]) == (
        2, 2)
    # every value of c1 and c2 is a str: hashed as it is, fit and transform
    # (the transform of the fitted table takes the fit's codes and says its)
    assert [s.attrs for s in _named(mine, "onehot.encode")] == [
        {"column": "c1", "path": "hashed", "objects": 4},
        {"column": "c2", "path": "hashed", "objects": 2}]
    (concat,) = _named(mine, "onehot.concat")
    # 4 + OTHER + null and 2 + OTHER + null columns of float32
    assert concat.attrs["bytes"] == ROWS * (6 + 4) * 4


def _combiner_span(spans):
    (s,) = [s for s in _named(spans, "stage.transform")
            if s.attrs["stage"] == "VectorsCombiner"]
    return s


def test_the_host_path_says_so(spans):
    """600 rows lie under the row constant: the host writes the blocks and
    joins them, and the whole matrix goes up."""
    _, mine = _root(spans, "workflow.train")
    assert [s.attrs["path"] for s in _named(mine, "onehot.expand")] == [
        "host", "host"]
    attrs = _combiner_span(mine).attrs
    assert (attrs["deviceInputs"], attrs["hostInputs"],
            attrs["h2dBytes"]) == (0, 2, ROWS * (4 + 10) * 4)


def test_the_device_path_says_so(monkeypatch):
    """Over the row constant: every ``onehot.expand`` says ``"device"``,
    ``onehot.concat`` still says the block's bytes, and the combiner says
    that the block stayed where it was and only the reals went up."""
    from transmogrifai_tpu.impl.feature import vectorizers
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", ROWS)
    ot.reset()
    ot.enable_tracing(True)
    try:
        wf, _ = _workflow(_df())
        wf.train()
        spans = ot.tracer().finished()
    finally:
        ot.reset()
    _, mine = _root(spans, "workflow.train")
    expands = _named(mine, "onehot.expand")
    assert [s.attrs for s in expands] == [
        {"column": "c1", "path": "device"}, {"column": "c2", "path": "device"}]
    (concat,) = _named(mine, "onehot.concat")
    assert concat.attrs["bytes"] == ROWS * (6 + 4) * 4
    attrs = _combiner_span(mine).attrs
    # x1, x2 and their null flags were filled on the chip too (PR 40): both
    # blocks stay where they are and nothing goes up
    assert (attrs["deviceInputs"], attrs["hostInputs"],
            attrs["h2dBytes"]) == (2, 0, 0)
    _real_spans_of_one_transform(mine, "device")


def _real_spans_of_one_transform(spans, path):
    """``realvec.fill`` and ``realvec.stack``, once a transform of the Real
    model, each with its ``path``: the benchmark's ``span_sum`` readers of
    ``fe_real_fill_s`` / ``fe_real_stack_s`` find a value on either path."""
    (stage,) = [s for s in _named(spans, "stage.transform")
                if s.attrs["stage"] == "RealVectorizerModel"]
    kids = sorted((s for s in spans if s.parent_id == stage.span_id),
                  key=lambda s: s.ts_ns)
    assert [(s.name, s.attrs["path"]) for s in kids] == [
        ("realvec.fill", path), ("realvec.stack", path)]
    fill, stack = kids
    # what went up: x1 and x2 as float32 and a mask each; the host path
    # counts what it filled, the four columns of the block
    assert fill.attrs["bytes"] == ROWS * ((2 * 4 + 2) if path == "device"
                                          else 4 * 4)
    assert (stack.attrs["columns"], stack.attrs["bytes"]) == (4, ROWS * 4 * 4)


def test_the_real_blocks_spans_are_there_on_the_host_path(spans):
    _, mine = _root(spans, "workflow.train")
    _real_spans_of_one_transform(mine, "host")


@pytest.mark.parametrize("values,path,objects,own", [
    (["a", "b", "a", None], "hashed", 2, False),
    # numpy's str: three objects a repeat, two levels
    (list(np.array(["a", "b", "a"])), "hashed", 3, False),
    ([3, 1, 3, None], "str_pass", 2, False),
    (["1", 1, True, 1.0], "str_pass", 4, False),
    ([None, None], "hashed", 0, False),                 # nothing to str()
    (["aa", "bb", "aa", None], "hashed", 0, True),
], ids=["str", "numpy_str", "ints", "mixed", "all_null", "object_a_row"])
def test_one_hot_spans_say_which_pass_ran(values, path, objects, own):
    """``path``: how values were resolved; ``objects``: the distinct objects
    met where rows were grouped by object first (``values * reps`` repeats
    the same objects), 0 where every row's value was resolved: the small
    pass, and a column that holds an object of its own in every row."""
    from transmogrifai_tpu.impl.feature import OneHotVectorizer
    from transmogrifai_tpu.types import PickList
    for reps in (1, 100):       # the small pass and pandas' say the same
        column = [v and (v + ".")[:-1] for v in values * reps] if own \
            else values * reps
        table = tg.FeatureTable.from_columns({"c": (PickList, column)})
        st = OneHotVectorizer()
        st.set_input(FeatureBuilder.PickList("c").extract_field().as_predictor())
        ot.reset()
        ot.enable_tracing(True)
        st.fit(table).transform_column(table)
        got = {s.name: s.attrs for s in ot.tracer().finished()}
        ot.reset()
        met = objects if reps > 1 else 0
        assert (got["onehot.count"]["path"],
                got["onehot.count"]["objects"]) == (path, met)
        assert got["onehot.encode"] == {"column": "c", "path": path,
                                        "objects": met}


def test_every_span_of_a_train_shares_the_roots_id(spans):
    root, mine = _root(spans, "workflow.train")
    assert root.parent_id is None and root.root_id == root.span_id
    names = collections.Counter(s.name for s in mine)
    for name in list(ONCE_A_TRAIN) + ["sweep.family", "stage.fit",
                                      "onehot.count", "onehot.encode"]:
        assert names[name] >= 1, name
    by_id = {s.span_id: s for s in spans}
    for s in mine:
        at = s
        while at.parent_id is not None:
            at = by_id[at.parent_id]
        assert at is root
    assert root.to_json()["root"] == root.span_id


def test_the_score_has_its_own_root_and_the_plans_three_spans(spans):
    train_root, _ = _root(spans, "workflow.train")
    root, mine = _root(spans, "workflow.score")
    assert root.root_id != train_root.root_id
    segs = _named(mine, "plan.segment")
    stage_in = _named(mine, "plan.stage_inputs")
    collect = _named(mine, "plan.collect")
    assert len(segs) >= 1 and len(stage_in) == len(segs) == len(collect)
    for a, b, c in zip(*(sorted(x, key=lambda s: s.ts_ns)
                         for x in (stage_in, segs, collect))):
        assert a.ts_ns + a.dur_ns <= b.ts_ns
        assert b.ts_ns + b.dur_ns <= c.ts_ns
        assert {"bytes", "transfers", "bucket"} <= set(a.attrs)
        assert a.attrs["bucket"] >= ROWS and a.attrs["bytes"] > 0
        assert c.attrs["outputs"] == b.attrs["outputs"]


def test_the_roots_carry_the_bytes_that_went_up(spans):
    train_root, _ = _root(spans, "workflow.train")
    score_root, mine = _root(spans, "workflow.score")
    # the combined feature matrix (12 columns) goes up once in a train
    assert train_root.attrs["h2dBytes"] >= ROWS * 12 * 4
    staged = sum(s.attrs["bytes"] for s in _named(mine, "plan.stage_inputs"))
    assert score_root.attrs["h2dBytes"] >= staged > 0


def test_tracing_off_makes_no_span_at_all(runs):
    spans, starts, _, _ = runs["off"]
    assert spans == [] and starts == []
    assert len(runs["on"][1]) == len(runs["on"][0]) > 20


def test_results_are_bit_equal_with_tracing_on_and_off(runs):
    _, _, scores_on, params_on = runs["on"]
    _, _, scores_off, params_off = runs["off"]
    assert np.array_equal(scores_on, scores_off)
    assert params_on.keys() == params_off.keys()
    for k in params_on:
        assert np.array_equal(params_on[k], params_off[k]), k


def test_a_span_enters_a_profiler_annotation_of_the_same_name(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("end " + self.name)

    monkeypatch.setattr(ot, "_ANNOTATION", Annotation)
    with ot.span("off"):
        pass
    assert entered == []
    ot.enable_tracing(True)
    with ot.span("outer"):
        with ot.span("inner"):
            pass
    assert entered == ["outer", "inner", "end inner", "end outer"]


# -- one host clock: the profiler reads the tracer --------------------------

def test_a_profiled_train_stays_on_the_planned_path_and_reads_the_tracer():
    from transmogrifai_tpu import plan as plan_mod
    df = _df()
    wf, pred = _workflow(df)
    wf.with_profiler()
    assert not ot.tracing_enabled()
    model = wf.train()
    assert not ot.tracing_enabled()          # on for the run only
    m = wf.profiler.app_metrics()
    assert {"OneHotVectorizer", "ModelSelector"} <= set(m["byStage"])
    assert {"fit", "transform"} <= set(m["byOp"])
    assert any(k.startswith("layer_") for k in m["byLayer"])
    assert m["numRecords"] == len(m["spans"]) > 0
    assert m["stageSecondsTotal"] == pytest.approx(
        sum(m["byStage"].values()))
    # the score is planned although a profiler is attached: its fused
    # segments are reported under their own name with their stage count
    assert model.profiler is not None
    before = plan_mod.cache_stats()
    model.score(df=df)
    after = plan_mod.cache_stats()
    assert after != before
    sm = model.profiler.app_metrics()
    assert "plan.segment" in sm["byStage"]
    segs = [s for s in sm["spans"] if s["name"] == "plan.segment.transform"]
    assert segs and all(s["args"]["stages"] >= 1 for s in segs)
    assert sm["byOp"].keys() == {"transform"}


# -- device names: named scopes inside programs that keep their names -------

def _sweep_program_text(family_name, grid):
    import jax.numpy as jnp
    import transmogrifai_tpu.models.trees  # noqa: F401  (registers forests)
    from transmogrifai_tpu.impl.tuning import validators
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    rng = np.random.RandomState(0)
    X = jnp.asarray(rng.randn(256, 5), jnp.float32)
    y = jnp.asarray(rng.rand(256) > .5, jnp.float32)
    ids = jnp.asarray(np.arange(256) % 3, jnp.uint8)
    family = MODEL_REGISTRY[family_name]
    garr = {k: np.asarray(v)
            for k, v in family.grid_to_arrays(grid).items()}
    prog, _ = validators._make_fused_program(
        family, garr, len(grid), 3, "binary", "AuPR", 2, False, False, None)
    assert prog.__name__ == "prog"       # sweep_s and refit_s match jit_prog
    return prog.lower(X, y, ids).as_text(debug_info=True)


@pytest.mark.parametrize("family,grid,scopes", [
    ("OpLogisticRegression", [{"regParam": 0.01, "elasticNetParam": 0.0}],
     ["linear.newton_cg"]),
    ("OpRandomForestClassifier", [{"maxDepth": 3, "numTrees": 4}],
     ["hist.build", "hist.split"])])
def test_a_family_program_names_its_branch_and_its_kernels(family, grid,
                                                           scopes):
    text = _sweep_program_text(family, grid)
    assert f'"jit(prog)/sweep.{family}/' in text
    # a nested jit's ops are named from its own root: the scope leads there
    for scope in scopes:
        assert f'{scope}/' in text, scope


def test_the_binned_metric_and_the_plans_chain_are_named():
    import jax.numpy as jnp
    from transmogrifai_tpu import plan as plan_mod
    from transmogrifai_tpu.ops import metrics as ops_metrics
    s = jnp.linspace(0., 1., 64)
    text = ops_metrics.aupr_masked.lower(
        s, (s > .5).astype(jnp.float32), s >= 0,
        binned=True).as_text(debug_info=True)
    assert "metrics.binned/" in text
    df = _df()
    wf, _ = _workflow(df)
    model = wf.train()
    from transmogrifai_tpu.readers.readers import dataframe_to_table
    table = dataframe_to_table(df, model.raw_features)
    plan = plan_mod.get_plan(model.stages, table, cat="score")
    segs = [p for kind, p in plan.steps if kind == "device"]
    assert segs and all(p.chain.__name__ == "chain" for p in segs)
    names = {f"stage.{type(st).__name__}" for p in segs for st in p.stages}
    assert "stage.SelectedModel" in names


def test_the_sweeps_device_copies_die_with_the_sweep_not_with_the_collector():
    """``validate``'s closures formed a reference cycle that kept the padded
    feature matrix, labels, fold ids and fold gather on the device until the
    cyclic collector happened to run: 0.5 GB at 1M x 105, before or after
    the refit allocated, by luck (tracing's few more objects a train moved
    the collector's schedule and with it the peak, PR 24)."""
    import gc

    import jax
    df = _df()
    wf, _ = _workflow(df)
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    gc.disable()
    try:
        model = wf.train()
        del model, wf
        left = [a for a in jax.live_arrays() if id(a) not in before
                and a.nbytes > 4096]
    finally:
        gc.enable()
    assert [(a.shape, a.nbytes) for a in left] == []
