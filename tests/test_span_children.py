"""The steps inside the host regions (PR 36): ``selector.prepare``,
``selector.evaluate`` and the ``stage.*`` spans of the SanityChecker, the
RealVectorizer, its model and the SelectedModel each have a child a step;
the plan's zero-row probe is one leaf span; the spans at layer boundaries
carry the fullest device's live bytes; and with tracing off none of it
exists."""
import collections
import gc

import jax
import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder, observability
from transmogrifai_tpu.impl.selector import factories
from transmogrifai_tpu.observability import trace as ot
from transmogrifai_tpu.workflow import OpWorkflow

# hyperparameters no other test uses (tests/test_span_layers.py says why)
SELECTORS = {
    "binary": (factories.BinaryClassificationModelSelector, [
        ("OpLogisticRegression", [{"regParam": 0.0136,
                                   "elasticNetParam": 0.0}]),
        ("OpRandomForestClassifier", [{"maxDepth": 3, "numTrees": 4,
                                       "minInfoGain": 0.00136}])]),
    "multiclass": (factories.MultiClassificationModelSelector, [
        ("OpLogisticRegression", [{"regParam": 0.0137,
                                   "elasticNetParam": 0.0}])]),
    "regression": (factories.RegressionModelSelector, [
        ("OpLinearRegression", [{"regParam": 0.0138,
                                 "elasticNetParam": 0.0}])]),
}
#: spans of one train of `_workflow`, by selector: constants of the
#: workflow's shape (its stages, families and splits), not of its rows
SPANS_A_TRAIN = {"binary": 44, "multiclass": 43, "regression": 43}
ROWS = 600


def _df(rows, problem, seed=11):
    rng = np.random.RandomState(seed)
    x1, x2 = rng.randn(rows), rng.randn(rows)
    c1 = rng.choice(["a", "b", "c", "d"], size=rows, p=[.4, .3, .2, .1])
    c2 = rng.choice(["u", "v"], size=rows)
    z = x1 + 0.5 * x2 + (c1 == "a") - (c2 == "u")
    y = {"binary": (z > 0).astype(float),
         "multiclass": np.digitize(z, [-1.0, 0.5]).astype(float),
         "regression": z + 0.1 * rng.randn(rows)}[problem]
    return pd.DataFrame({"x1": x1, "x2": x2, "c1": c1, "c2": c2, "y": y})


def _workflow(df, problem, extra=()):
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real("x1").extract_field().as_predictor(),
             FeatureBuilder.Real("x2").extract_field().as_predictor(),
             FeatureBuilder.PickList("c1").extract_field().as_predictor(),
             FeatureBuilder.PickList("c2").extract_field().as_predictor(),
             *extra]
    checked = tg.transmogrify(feats).sanity_check(label)
    factory, models = SELECTORS[problem]
    pred = (factory.with_cross_validation(models=models)
            .set_input(label, checked).get_output())
    return OpWorkflow().set_input_dataset(df).set_result_features(pred)


def _traced_train(wf):
    """(the fitted model, the spans of its train)."""
    gc.collect()      # a dropped model lives on in cycles: not this train's
    ot.reset()
    ot.enable_tracing(True)
    try:
        model = wf.train()
        spans = ot.tracer().finished()
    finally:
        observability.reset()    # a module's fixture may leave nothing
    (root,) = [s for s in spans if s.name == "workflow.train"]
    return model, [s for s in spans if s.root_id == root.span_id]


@pytest.fixture(scope="module", params=sorted(SELECTORS))
def train(request):
    """One traced train of each stock selector, and a second of a table
    with other rows."""
    problem = request.param
    _, spans = _traced_train(_workflow(_df(ROWS, problem), problem))
    _, other = _traced_train(_workflow(_df(ROWS + 300, problem), problem))
    return problem, spans, other


@pytest.fixture(scope="module")
def warm(train):
    """Two more trains of ``train``'s first table: its programs are
    compiled, so a region lasts its work (milliseconds, where the first
    train's compiles make it hundreds of them)."""
    problem = train[0]
    return [_traced_train(_workflow(_df(ROWS, problem), problem))[1]
            for _ in range(2)]


def _steps(spans, parent):
    """(the region, its children by start, the steps' names)."""
    region, steps = _parents(spans)[parent]
    kids = sorted((s for s in spans if s.parent_id == region.span_id
                   and not s.name.startswith("mesh.")),
                  key=lambda s: s.ts_ns)
    return region, kids, steps


def _stage(spans, name, stage):
    (s,) = [s for s in spans
            if s.name == name and s.attrs.get("stage") == stage]
    return s


def _parents(spans):
    (prepare,) = [s for s in spans if s.name == "selector.prepare"]
    (evaluate,) = [s for s in spans if s.name == "selector.evaluate"]
    return {
        "selector.prepare": (prepare, ["prepare.labels", "prepare.split",
                                       "prepare.balance", "prepare.gather"]),
        "selector.evaluate": (evaluate, ["evaluate.rows", "evaluate.predict",
                                         "evaluate.metrics"] * 2),
        "checker": (_stage(spans, "stage.fit", "SanityChecker"),
                    ["sanity.sample", "sanity.stats", "sanity.collect",
                     "sanity.decide"]),
        "real fit": (_stage(spans, "stage.fit", "RealVectorizer"),
                     ["realvec.stats"]),
        "real transform": (_stage(spans, "stage.transform",
                                  "RealVectorizerModel"),
                           ["realvec.fill", "realvec.stack"]),
        "closing transform": (_stage(spans, "stage.transform",
                                     "SelectedModel"),
                              ["predict.pad", "predict.parts",
                               "predict.unmap", "predict.column"]),
    }


PARENTS = ["selector.prepare", "selector.evaluate", "checker", "real fit",
           "real transform", "closing transform"]


def _uncovered(spans, parent):
    """(the region's time, the parts of it that no step covers: before the
    first step, between each two, after the last)."""
    region, kids, _ = _steps(spans, parent)
    edges = [region.ts_ns, *(t for s in kids
                             for t in (s.ts_ns, s.ts_ns + s.dur_ns)),
             region.ts_ns + region.dur_ns]
    return region.dur_ns, [b - a for a, b in zip(edges[::2], edges[1::2])]


@pytest.mark.parametrize("parent", PARENTS)
def test_every_step_is_one_child_in_order_and_the_steps_cover_the_region(
        train, warm, parent):
    for spans in (train[1], *warm):
        region, kids, steps = _steps(spans, parent)
        assert [s.name for s in kids] == steps
        for a, b in zip(kids, kids[1:]):
            assert a.ts_ns + a.dur_ns <= b.ts_ns
        assert region.ts_ns <= kids[0].ts_ns
        assert (kids[-1].ts_ns + kids[-1].dur_ns
                <= region.ts_ns + region.dur_ns)
    # what the CODE leaves outside the steps (a pass over the rows, a wait
    # for the device) it leaves in the same gap of every train; what the
    # machine puts there (a thread off its core for 4 ms, a collection) it
    # puts where it likes. So each gap counts for the shorter of its two
    # warm trains', and a loaded machine has to hit one gap twice. The
    # steps' own entries and exits are 5-9 % of the regions of two
    # milliseconds and more, and up to 14 % beside seven busy loops on
    # eight cores (PR 46): a fifth of the region, or 600 us for a region of
    # microseconds (a column into the table, the spans themselves)
    (dur, gaps), (dur2, gaps2) = (_uncovered(w, parent) for w in warm)
    outside = sum(map(min, gaps, gaps2))
    assert 0 <= outside <= max(0.2 * min(dur, dur2), 600_000), (
        gaps, dur, gaps2, dur2)


def test_the_steps_say_what_they_worked_on(train):
    problem, spans, _ = train
    by = collections.defaultdict(list)
    for s in spans:
        by[s.name].append(s)
    (labels,), (split,), (balance,), (gather,) = (
        by["prepare." + k] for k in ("labels", "split", "balance", "gather"))
    assert labels.attrs == {"rows": ROWS}
    assert split.attrs["rows"] == ROWS
    assert split.attrs["trainRows"] + split.attrs["testRows"] == ROWS
    assert balance.attrs["rowsIn"] == split.attrs["trainRows"]
    assert balance.attrs["splitter"] == {
        "binary": "DataBalancer", "multiclass": "DataCutter",
        "regression": "DataSplitter"}[problem]
    assert gather.attrs == {"rows": balance.attrs["rowsKept"]}
    for name in ("evaluate.rows", "evaluate.predict", "evaluate.metrics"):
        assert [(s.attrs["split"], s.attrs["rows"], s.attrs["path"])
                for s in by[name]] == [
            ("train", split.attrs["trainRows"], "device"),
            ("holdout", split.attrs["testRows"], "device")]
    (sample,), (stats,), (decide,) = (
        by["sanity." + k] for k in ("sample", "stats", "decide"))
    assert sample.attrs == {"rows": ROWS, "sampleRows": ROWS}
    assert stats.attrs["features"] == decide.attrs["features"] == 14
    assert 0 <= decide.attrs["dropped"] < 14
    (fit_stats,), (fill,), (stack,) = (
        by["realvec." + k] for k in ("stats", "fill", "stack"))
    assert fit_stats.attrs == {"path": "host"}
    # x1, x2 and their null flags as float32
    assert fill.attrs == {"path": "host", "bytes": ROWS * 4 * 4}
    assert stack.attrs == {"path": "host", "columns": 4,
                           "bytes": ROWS * 4 * 4}
    (pad,), (column,) = by["predict.pad"], by["predict.column"]
    assert pad.attrs["rows"] == ROWS <= pad.attrs["paddedRows"]
    assert column.attrs["bytes"] > 0


def test_the_spans_of_a_train_are_a_constant_of_the_workflows_shape(train):
    problem, spans, other = train
    assert len(spans) == len(other) == SPANS_A_TRAIN[problem]
    assert (collections.Counter(s.name for s in spans)
            == collections.Counter(s.name for s in other))


BOUNDARIES = ["workflow.train", "stage.fit", "stage.transform",
              "selector.prepare", "sweep.family", "sweep.collect",
              "selector.refit", "selector.evaluate"]


def test_layer_boundaries_carry_live_bytes_and_steps_do_not(train):
    _, spans, _ = train
    for s in spans:
        has = {"hbmLiveStart", "hbmLiveEnd"} <= set(s.attrs)
        assert has == (s.name in BOUNDARIES), s.name
    (root,) = [s for s in spans if s.name == "workflow.train"]
    # a train leaves its table and its model on the device
    assert root.attrs["hbmLiveEnd"] > root.attrs["hbmLiveStart"] >= 0
    (collect,) = [s for s in spans if s.name == "sweep.collect"]
    assert collect.attrs["hbmLiveStart"] > 0


def test_tracing_off_makes_no_span_and_asks_no_device(monkeypatch):
    """Every new call site is one flag check: no ``Span`` is made, the
    live-bytes helper is never called and no device is asked for its
    ``memory_stats``."""
    def never(*a, **kw):
        raise AssertionError("asked for device memory with tracing off")
    monkeypatch.setattr(ot, "live_device_bytes", never)
    monkeypatch.setattr(ot, "fullest_device_stats", never)
    monkeypatch.setattr(type(jax.local_devices()[0]), "memory_stats", never,
                        raising=False)
    made = []
    real_start = ot.Tracer.start
    monkeypatch.setattr(ot.Tracer, "start", lambda self, name, *a, **kw: (
        made.append(name), real_start(self, name, *a, **kw))[1])
    assert not ot.tracing_enabled()
    df = _df(ROWS, "binary")
    model = _workflow(df, "binary").train()
    model.score(df=df)
    assert made == [] and not ot.tracer().finished()


# -- the plan's probe ---------------------------------------------------------

def test_a_plans_probe_is_one_leaf_span():
    """A layer with two device-capable stages (the Real and the Integral
    fills) is planned at every train, and the plan's zero-row probe runs
    every stage of the layer: one ``plan.probe`` span a plan built, the
    stages' own spans (``onehot.*`` with ``bytes`` 0, ``realvec.*``) not
    recorded under it, and none where the plan came from the cache."""
    df = _df(ROWS, "binary")
    df["k"] = np.random.RandomState(3).randint(0, 5, size=ROWS)
    extra = [FeatureBuilder.Integral("k").extract_field().as_predictor()]
    model, spans = _traced_train(_workflow(df, "binary", extra))
    by_id = {s.span_id: s for s in spans}
    (probe,) = [s for s in spans if s.name == "plan.probe"]
    (compile_,) = [s for s in spans if s.name == "plan.compile"]
    assert probe.parent_id == compile_.span_id
    assert probe.attrs["stages"] == compile_.attrs["stages"] == 3
    assert probe.leaf and not [s for s in spans
                               if s.parent_id == probe.span_id]
    # the layer's own run: every one-hot span is a table's
    onehot = [s for s in spans if s.name.startswith("onehot.")]
    assert len([s for s in onehot if s.name == "onehot.expand"]) == 2
    (concat,) = [s for s in onehot if s.name == "onehot.concat"]
    assert concat.attrs["bytes"] == ROWS * (6 + 4) * 4
    for s in onehot:
        at = s
        while at.parent_id is not None:
            at = by_id[at.parent_id]
            assert at.name != "plan.probe"
    # a score plans once, and the second score finds the plan
    ot.reset()
    ot.enable_tracing(True)
    try:
        model.score(df=df)
        first = [s.name for s in ot.tracer().finished()]
        ot.tracer().clear()
        model.score(df=df)
        second = [s.name for s in ot.tracer().finished()]
    finally:
        ot.reset()
    assert first.count("plan.probe") == first.count("plan.compile") == 1
    assert "plan.probe" not in second and "plan.compile" not in second
    assert len(first) - len(second) == 2


def test_a_leaf_span_mutes_its_thread_only():
    import threading
    ot.enable_tracing(True)
    seen = []

    def other_thread():
        with ot.span("other") as s:
            seen.append(s)
    with ot.span("outer", leaf=True) as outer:
        with ot.span("inner") as inner:
            inner.set_attr(x=1)          # the inert span takes it
            ot.add_event("noted")
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert inner is ot.NULL_SPAN and seen[0] is not ot.NULL_SPAN
    assert sorted(s.name for s in ot.tracer().finished()) == [
        "other", "outer"]
    assert [e[0] for e in outer.events] == ["noted"]


# -- live bytes ---------------------------------------------------------------

def test_live_bytes_are_the_fullest_devices(monkeypatch):
    """Four devices: the helper asks each, not the first. Where the
    backend keeps statistics it takes the device with the most bytes in
    use; the CPU keeps none, and the live arrays' shards are counted."""
    class Chip:
        def __init__(self, in_use):
            self.in_use = in_use

        def memory_stats(self):
            return {"bytes_in_use": self.in_use,
                    "peak_bytes_in_use": 2 * self.in_use}
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Chip(b) for b in (300, 100, 900, 200)])
    assert ot.fullest_device_stats() == {"bytes_in_use": 900,
                                         "peak_bytes_in_use": 1800}
    assert ot.live_device_bytes() == 900
    from transmogrifai_tpu.observability import devicemem
    monkeypatch.setattr(devicemem, "_stats_supported", None)
    assert devicemem.memory_stats() == {"bytes_in_use": 900,
                                        "peak_bytes_in_use": 1800}
    monkeypatch.undo()
    assert ot.fullest_device_stats() is None        # the CPU says nothing
    devs = jax.devices()[:4]
    before = ot.live_device_bytes()
    small = jax.device_put(np.zeros(1000, np.float32), devs[0])
    big = jax.device_put(np.zeros(1 << 20, np.float32), devs[2])
    assert ot.live_device_bytes() >= big.nbytes
    assert ot.live_device_bytes() < before + big.nbytes + small.nbytes
    ot.enable_tracing(True)
    with ot.span("boundary", hbm=True) as s:
        held = jax.device_put(np.zeros(1 << 20, np.float32), devs[2])
    assert s.attrs["hbmLiveEnd"] - s.attrs["hbmLiveStart"] == held.nbytes
    del small, big, held


def test_a_mesh_train_reads_all_its_chips_and_shows_the_table_kept(
        monkeypatch):
    """Under ``with_mesh(data=4)`` the rows lie in quarters on four devices
    and ``hbmLive*`` is one device's share, not the table; the Real fit
    takes its sharded path (``realvec.stack`` then ``realvec.stats``); and a
    model kept alive, as the benchmark's closed loop keeps the train
    before, shows in the next train's ``hbmLiveStart``."""
    from transmogrifai_tpu.parallel import MeshSpec, make_mesh
    monkeypatch.setenv("TG_MESH_FORCE", "1")
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    rows = 2000
    df = _df(rows, "binary")
    first, spans1 = _traced_train(_workflow(df, "binary").with_mesh(mesh))
    second, spans2 = _traced_train(_workflow(df, "binary").with_mesh(mesh))
    (root1,) = [s for s in spans1 if s.name == "workflow.train"]
    (root2,) = [s for s in spans2 if s.name == "workflow.train"]
    start1, end1 = root1.attrs["hbmLiveStart"], root1.attrs["hbmLiveEnd"]
    start2, end2 = root2.attrs["hbmLiveStart"], root2.attrs["hbmLiveEnd"]
    # the first model (its train_table: a device's quarter of the combined
    # and of the checked matrix at least) is alive when the second train
    # opens; what the first left in cycles went with the collection between
    assert start1 + 2 * (rows // 4) * 10 * 4 < start2 <= end1
    assert end2 > start2
    # a quarter of the rows a device: well under the whole checked matrix
    (prepare,) = [s for s in spans2 if s.name == "selector.prepare"]
    gathered = prepare.attrs["hbmLiveEnd"] - prepare.attrs["hbmLiveStart"]
    (gather,) = [s for s in spans2 if s.name == "prepare.gather"]
    assert 0 < gathered < gather.attrs["rows"] * 14 * 4 / 2
    fit = _stage(spans2, "stage.fit", "RealVectorizer")
    kids = sorted((s for s in spans2 if s.parent_id == fit.span_id
                   and s.name.startswith("realvec.")), key=lambda s: s.ts_ns)
    assert [s.name for s in kids] == ["realvec.stack", "realvec.stats"]
    assert kids[0].attrs == {"columns": 2, "bytes": rows * 2 * 4}
    assert kids[1].attrs == {"path": "mesh"}
    del first, second
