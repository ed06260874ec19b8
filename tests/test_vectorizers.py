"""Vectorizer tests (model: reference RealVectorizerTest, OpOneHotVectorizerTest,
SmartTextVectorizerTest, VectorsCombinerTest)."""
import numpy as np
import pytest

from transmogrifai_tpu import FeatureBuilder, FeatureTable
from transmogrifai_tpu.types import (
    Real, RealNN, Integral, Binary, PickList, Text, TextList, MultiPickList)
from transmogrifai_tpu.impl.feature import (
    RealVectorizer, IntegralVectorizer, BinaryVectorizer, OneHotVectorizer,
    SmartTextVectorizer, HashingVectorizer, TextTokenizer, VectorsCombiner,
    transmogrify)
from transmogrifai_tpu.vector_metadata import NULL_INDICATOR, OTHER_INDICATOR
from transmogrifai_tpu.workflow import OpWorkflow


def test_real_vectorizer_mean_fill_and_null_track():
    age = FeatureBuilder.Real("age").extract_field().as_predictor()
    fare = FeatureBuilder.Real("fare").extract_field().as_predictor()
    tbl = FeatureTable.from_columns({
        "age": (Real, [10.0, None, 30.0]),
        "fare": (Real, [1.0, 2.0, 3.0])})
    st = RealVectorizer()
    st.set_input(age, fare)
    model = st.fit(tbl)
    col = model.transform_column(tbl)
    vals = np.asarray(col.values)
    # age: filled mean=20, null indicators [0,1,0]; fare: no nulls
    assert np.allclose(vals[:, 0], [10, 20, 30])
    assert np.allclose(vals[:, 1], [0, 1, 0])
    assert np.allclose(vals[:, 2], [1, 2, 3])
    vm = col.metadata["vector_meta"]
    assert vm.columns[1].indicator_value == NULL_INDICATOR
    assert vm.columns[0].parent_feature_name == "age"
    # row dual parity
    assert model.transform_row({"age": None, "fare": 5.0}) == [20.0, 1.0, 5.0, 0.0]


def test_integral_vectorizer_mode_fill():
    x = FeatureBuilder.Integral("x").extract_field().as_predictor()
    tbl = FeatureTable.from_columns({"x": (Integral, [1, 2, 2, None, 3])})
    st = IntegralVectorizer()
    st.set_input(x)
    model = st.fit(tbl)
    vals = np.asarray(model.transform_column(tbl).values)
    assert np.allclose(vals[:, 0], [1, 2, 2, 2, 3])  # mode=2
    assert np.allclose(vals[:, 1], [0, 0, 0, 1, 0])


def test_one_hot_vectorizer():
    color = FeatureBuilder.PickList("color").extract_field().as_predictor()
    data = ["red"] * 5 + ["blue"] * 3 + ["green"] * 1 + [None]
    tbl = FeatureTable.from_columns({"color": (PickList, data)})
    st = OneHotVectorizer(top_k=2, min_support=2)
    st.set_input(color)
    model = st.fit(tbl)
    col = model.transform_column(tbl)
    vals = np.asarray(col.values)
    vm = col.metadata["vector_meta"]
    # columns: red, blue, OTHER, null
    assert [c.indicator_value for c in vm.columns] == \
        ["red", "blue", OTHER_INDICATOR, NULL_INDICATOR]
    assert vals.shape == (10, 4)
    assert vals[0].tolist() == [1, 0, 0, 0]
    assert vals[5].tolist() == [0, 1, 0, 0]
    assert vals[8].tolist() == [0, 0, 1, 0]   # green below minSupport → OTHER
    assert vals[9].tolist() == [0, 0, 0, 1]   # null


def test_one_hot_multipicklist():
    tags = FeatureBuilder.MultiPickList("tags").extract_field().as_predictor()
    data = [{"a", "b"}, {"a"}, set(), None]
    tbl = FeatureTable.from_columns({"tags": (MultiPickList, data)})
    st = OneHotVectorizer(top_k=5, min_support=1)
    st.set_input(tags)
    model = st.fit(tbl)
    col = model.transform_column(tbl)
    vm = col.metadata["vector_meta"]
    vals = np.asarray(col.values)
    idx = {c.indicator_value: c.index for c in vm.columns}
    assert vals[0, idx["a"]] == 1 and vals[0, idx["b"]] == 1
    assert vals[3, idx[NULL_INDICATOR]] == 1


def test_smart_text_pivot_vs_hash():
    lowcard = FeatureBuilder.Text("lo").extract_field().as_predictor()
    highcard = FeatureBuilder.Text("hi").extract_field().as_predictor()
    n = 60
    lo_vals = ["a" if i % 2 else "b" for i in range(n)]
    hi_vals = [f"word{i} text{i%7}" for i in range(n)]
    tbl = FeatureTable.from_columns({"lo": (Text, lo_vals), "hi": (Text, hi_vals)})
    st = SmartTextVectorizer(max_cardinality=10, min_support=1, num_hashes=16)
    st.set_input(lowcard, highcard)
    model = st.fit(tbl)
    col = model.transform_column(tbl)
    vm = col.metadata["vector_meta"]
    # lo → pivot (2 vals + OTHER + null), hi → hash (16 + null)
    assert col.width == (2 + 1 + 1) + (16 + 1)
    lo_cols = [c for c in vm.columns if c.parent_feature_name == "lo"]
    assert {c.indicator_value for c in lo_cols} >= {"a", "b"}


def test_hashing_vectorizer_shared_space():
    t1 = FeatureBuilder.TextList("t1").extract_field().as_predictor()
    t2 = FeatureBuilder.TextList("t2").extract_field().as_predictor()
    tbl = FeatureTable.from_columns({
        "t1": (TextList, [["x", "y"], ["x"]]),
        "t2": (TextList, [["z"], []])})
    shared = HashingVectorizer(num_hashes=8, shared_hash_space=True)
    shared.set_input(t1, t2)
    vals = np.asarray(shared.transform_column(tbl).values)
    assert vals.shape == (2, 8)
    assert vals[0].sum() == 3.0  # x, y, z
    sep = HashingVectorizer(num_hashes=8, shared_hash_space=False)
    sep.set_input(t1, t2)
    assert np.asarray(sep.transform_column(tbl).values).shape == (2, 16)


def test_tokenizer():
    txt = FeatureBuilder.Text("t").extract_field().as_predictor()
    tok = TextTokenizer()
    out = txt.transform_with(tok)
    assert tok.transform_fn("Hello, World! 123") == ["hello", "world", "123"]
    assert tok.transform_fn(None) == []


def test_transmogrify_end_to_end():
    import pandas as pd
    df = pd.DataFrame({
        "age": [20.0, None, 40.0, 35.0] * 5,
        "cnt": [1, 2, 2, None] * 5,
        "vip": [True, False, None, True] * 5,
        "color": ["red", "blue", "red", None] * 5,
        "label": [0.0, 1.0, 1.0, 0.0] * 5,
    })
    resp, feats = FeatureBuilder.from_dataframe(df, response="label")
    from transmogrifai_tpu.types import PickList
    # re-type color as PickList for pivoting
    feats = [f for f in feats if f.name != "color"]
    color = FeatureBuilder.PickList("color").extract_field().as_predictor()
    feats.append(color)
    fv = transmogrify(feats)
    model = OpWorkflow().set_input_dataset(df).set_result_features(fv).train()
    scored = model.score(df=df)
    col = scored[fv.name]
    vm = col.metadata["vector_meta"]
    assert col.width == vm.size
    parents = {c.parent_feature_name for c in vm.columns}
    assert parents == {"age", "cnt", "vip", "color"}
    # deterministic order: groups sorted, features sorted within group
    assert np.asarray(col.values).shape[0] == 20


# -- values to codes by one hash pass: same answers as the per-row loops ----

def _reference_pivot(fit, transform, top_k, min_support, track_nulls, multi):
    """The per-row loops the hash pass replaced, as plain as they come:
    (vocabulary, matrix) of one column fitted on ``fit`` and applied to
    ``transform``, each a (values, valid mask) pair."""
    import collections
    cnt = collections.Counter()
    for v, ok in zip(*fit):
        if ok:
            cnt.update((v or ()) if multi else [str(v)])
    top = sorted((v for v, c in cnt.items() if c >= min_support),
                 key=lambda v: (-cnt[v], v))[:top_k]
    index = {v: i for i, v in enumerate(top)}
    k = len(top)
    mat = np.zeros((len(transform[0]), k + 1 + int(track_nulls)), np.float32)
    for i, (v, ok) in enumerate(zip(*transform)):
        if ok:
            for level in ((v or ()) if multi else [str(v)]):
                mat[i, index.get(level, k)] = 1.0
        elif track_nulls:
            mat[i, k + 1] = 1.0
    return top, mat


def _pivot_case(values, masked=(), transform=None, top_k=20, min_support=1,
                track_nulls=True):
    """``masked``: positions the column's mask calls null whatever they
    hold; ``transform``: values met after the fit (default: the fit's)."""
    return dict(values=values, masked=masked, transform=transform,
                top_k=top_k, min_support=min_support, track_nulls=track_nulls)


PIVOT_CASES = {
    "all_str": _pivot_case(["red"] * 5 + ["blue"] * 3 + ["green"]),
    "nulls_tracked": _pivot_case(
        ["a", None, "b", "a", None, "masked", "b", "a"], masked=(5,)),
    "nulls_untracked": _pivot_case(
        ["a", None, "b", "a", None, "masked", "b", "a"], masked=(5,),
        track_nulls=False),
    "all_null": _pivot_case([None, None, None]),
    "ints": _pivot_case([3, 1, 3, 2, 3, 1, None, 10]),
    # str() keeps apart what == and hash() merge
    "mixed_1_True_str1_float1": _pivot_case(
        [1, True, "1", 1.0, True, "1", 1, 1, False, 0, 0.0, "0"]),
    "nan_held_valid": _pivot_case(
        ["a", float("nan"), "nan", "a", None], masked=(4,)),
    "numpy_str": _pivot_case(list(np.array(["u", "v", "u", "w", "u"]))),
    "ties_in_count": _pivot_case(["b", "a", "c", "b", "a", "c", "d"],
                                 top_k=2),
    "min_support_cuts": _pivot_case(["x"] * 4 + ["y"] * 3 + ["z"] * 2 + ["w"],
                                    min_support=3),
    "more_levels_than_top_k": _pivot_case(
        [f"l{i % 7}" for i in range(30)] + ["l0", "l1", "l1"], top_k=3),
    "unseen_at_transform": _pivot_case(
        ["a", "b", "a"], transform=["b", "never seen", None, "a", 7]),
    "empty_table": _pivot_case([]),
    "one_row": _pivot_case(["only"]),
    "one_null_row": _pivot_case([None]),
}
#: repeated past the point where the hash pass hands over to pandas
PIVOT_PARAMS = [(name, reps) for name in PIVOT_CASES for reps in (1, 64)
                if not (reps > 1 and name in ("empty_table", "one_row",
                                              "one_null_row"))]


def _scalar_column(ftype, values, masked, reps):
    from transmogrifai_tpu.table import Column, _is_missing
    arr = np.empty(len(values) * reps, dtype=object)
    arr[:] = list(values) * reps
    mask = np.array([not _is_missing(v) for v in arr], dtype=bool)
    for i in masked:
        mask[i::len(values)] = False
    return Column(ftype, arr, mask)


@pytest.mark.parametrize("stage", ["one_hot", "smart_text"])
@pytest.mark.parametrize("name,reps", PIVOT_PARAMS)
def test_pivot_equals_the_per_row_loops(stage, name, reps):
    from transmogrifai_tpu.impl.feature import vectorizers
    case = PIVOT_CASES[name]
    ftype = PickList if stage == "one_hot" else Text
    feat = getattr(FeatureBuilder, ftype.__name__)("c").extract_field().as_predictor()
    fit_col = _scalar_column(ftype, case["values"], case["masked"], reps)
    seen = (fit_col if case["transform"] is None
            else _scalar_column(ftype, case["transform"], (), reps))
    for c in (fit_col, seen):   # the repeated cases reach the pandas pass
        assert (reps == 1 or name == "all_null"
                or c.mask.sum() > vectorizers._SMALL_HASH_PASS)
    kw = dict(top_k=case["top_k"], min_support=case["min_support"] * reps,
              track_nulls=case["track_nulls"])
    st = (OneHotVectorizer(**kw) if stage == "one_hot"
          else SmartTextVectorizer(max_cardinality=10 ** 6, **kw))
    st.set_input(feat)
    model = st.fit(FeatureTable({"c": fit_col}, len(fit_col)))
    col = model.transform_column(FeatureTable({"c": seen}, len(seen)))

    vocab, mat = _reference_pivot(
        (fit_col.values, fit_col.mask), (seen.values, seen.mask),
        kw["top_k"], kw["min_support"], kw["track_nulls"], multi=False)
    got = model.vocabs[0] if stage == "one_hot" else model.plans[0]["vocab"]
    assert got == vocab and all(type(v) is str for v in got)
    vals = np.asarray(col.values)
    assert vals.dtype == np.float32 and vals.shape == mat.shape
    assert np.array_equal(vals, mat)
    want = vocab + [OTHER_INDICATOR] + (
        [NULL_INDICATOR] if kw["track_nulls"] else [])
    assert [(c.parent_feature_name, c.parent_feature_type, c.grouping,
             c.indicator_value, c.descriptor_value, c.index)
            for c in col.metadata["vector_meta"].columns] == [
        ("c", ftype.__name__, "c", v, None, i) for i, v in enumerate(want)]
    # the row dual goes through the same code
    for i in range(min(len(seen), 12)):
        row = {"c": seen.values[i] if seen.mask[i] else None}
        assert model.transform_row(row) == mat[i].tolist()


@pytest.mark.parametrize("track_nulls", [True, False])
def test_pivot_of_sets_equals_the_per_row_loops(track_nulls):
    from transmogrifai_tpu.table import Column
    tags = FeatureBuilder.MultiPickList("c").extract_field().as_predictor()
    data = [{"a", "b"}, {"a"}, set(), None, {"c", "a"}, {"b"}, {"zz"}] * 3
    seen = data + [{"new", "a"}, None]
    st = OneHotVectorizer(top_k=3, min_support=3, track_nulls=track_nulls)
    st.set_input(tags)
    fit_col = Column.of_values(MultiPickList, data)
    seen_col = Column.of_values(MultiPickList, seen)
    model = st.fit(FeatureTable({"c": fit_col}, len(data)))
    col = model.transform_column(FeatureTable({"c": seen_col}, len(seen)))
    vocab, mat = _reference_pivot(
        (fit_col.values, fit_col.mask), (seen_col.values, seen_col.mask),
        3, 3, track_nulls, multi=True)
    assert model.vocabs[0] == vocab == ["a", "b", "c"]   # "zz" ties, cut
    assert np.array_equal(np.asarray(col.values), mat)
    assert [c.indicator_value for c in col.metadata["vector_meta"].columns] \
        == vocab + [OTHER_INDICATOR] + ([NULL_INDICATOR] if track_nulls else [])


def test_hashing_raw_mixed_objects_would_not_pass(monkeypatch):
    """What the mixed case is there for: a hash pass over the raw objects,
    stringified afterwards, merges ``1``, ``True`` and ``1.0``."""
    from transmogrifai_tpu.impl.feature import vectorizers

    def raw_hash(vals, m):
        import pandas as pd
        valid = vals[m]
        sub, uniques = pd.factorize(valid)
        levels = [str(u) for u in uniques]
        codes = np.full(len(vals), -1, dtype=np.intp)
        codes[m] = sub
        return codes, dict(zip(levels, np.bincount(
            sub, minlength=len(levels)).tolist())), "hashed", 0

    monkeypatch.setattr(vectorizers, "_factorize_valid", raw_hash)
    with pytest.raises(AssertionError):
        test_pivot_equals_the_per_row_loops(
            "one_hot", "mixed_1_True_str1_float1", 64)
    with pytest.raises(AssertionError):
        test_pivot_equals_the_per_row_loops(
            "smart_text", "mixed_1_True_str1_float1", 1)
    test_pivot_equals_the_per_row_loops("one_hot", "all_str", 64)


# -- rows grouped by object before any value is hashed (PR 37) ---------------

def _reference_factorize(vals, m):
    """``_factorize_valid`` as plain as it comes: a dict over ``str(v)`` of
    the valid rows. ``path`` is ``"hashed"`` where every valid value is a
    ``str`` and ``str()`` merges none of them."""
    seen, counts = {}, {}
    codes = np.full(len(vals), -1, dtype=np.intp)
    for i, (v, ok) in enumerate(zip(vals, m)):
        if ok:
            codes[i] = seen.setdefault(str(v), len(seen))
            counts[str(v)] = counts.get(str(v), 0) + 1
    valid = [v for v, ok in zip(vals, m) if ok]
    hashed = (all(isinstance(v, str) for v in valid)
              and len(set(valid)) == len(seen))
    return codes, counts, "hashed" if hashed else "str_pass"


def _objects(values):
    arr = np.empty(len(values), dtype=object)
    arr[:] = values
    return arr


def _fresh(values):
    """Every value as an object of its own (a ``csv`` loop, ``astype(str)``)."""
    return _objects([(v + ".")[:-1] for v in values])


class _Lower(str):
    """A ``str`` subclass with a ``str()`` of its own: equal levels from
    values the hash keeps apart."""
    def __str__(self):
        return self.lower()


def _object_cases():
    """name -> (values, mask or None, objects: what the count pass must say,
    None where either answer is sound)."""
    rng = np.random.RandomState(37)
    names = _objects([f"level{i}" for i in range(40)])
    draws = rng.choice(40, size=5000, p=np.arange(40, 0, -1) / 820.0)
    interned = names[draws]
    a_row = _fresh(list(interned))
    mostly_shared = interned.copy()
    mostly_shared[::50] = a_row[::50]
    mostly_a_row = a_row.copy()
    mostly_a_row[::50] = interned[::50]
    per_chunk = np.concatenate([_fresh(list(names))[draws[:2500]],
                                _fresh(list(names))[draws[2500:]]])
    some_null = interned.copy()
    mask = rng.rand(5000) > 0.2
    some_null[~mask] = None
    wide = _objects([None] * 10000)
    wide[1::2] = interned
    late = _objects(["early"] * 150000)
    late[140000:] = names[0]
    late[149000] = names[1]
    few = ["red", "blue", "red", "green"]
    return {
        "interned": (interned, None, 40),
        "an_object_a_row": (a_row, None, 0),
        "mostly_shared": (mostly_shared, None, 140),
        "mostly_an_object_a_row": (mostly_a_row, None, 0),
        "an_object_a_level_and_chunk": (per_chunk, None, 80),
        "all_unique": (_objects([f"id{i}" for i in range(3000)]), None, 0),
        "mixed_1_True_str1_float1": (_objects(["1", 1, True, 1.0] * 100),
                                     None, 4),
        "ints": (_objects([3, 1, 300, 3, 1] * 60), None, 3),
        "str_subclass": (_objects([_Lower("Aa"), _Lower("aa"), "b"] * 50),
                         None, 3),
        "numpy_str": (_objects(list(np.array(["u", "v", "u", "w"])) * 50),
                      None, 4),
        "none_under_a_partial_mask": (some_null, mask, 40),
        "none_held_valid": (_objects(["a", None, "a", "b"] * 50), None, 3),
        "all_null": (_objects([None] * 300), np.zeros(300, dtype=bool), 0),
        "sliced_view": (wide[5001:], np.arange(4999) % 2 == 0, 40),
        "strided_view": (wide[1::2], None, 40),
        "reversed_view": (interned[::-1], None, 40),
        "a_level_first_met_in_the_third_block": (late, None, 3),
        "rows_128": (_objects(few * 32), None, 0),
        "rows_129": (_objects(few * 32 + ["red"]), None, 3),
        "rows_0": (_objects([]), None, 0),
    }


OBJECT_CASES = _object_cases()


@pytest.mark.parametrize("name", list(OBJECT_CASES))
def test_count_pass_by_object_equals_the_dict_over_str(name):
    """Codes, counts with their key order, and path as the per-row dict
    gives them, whether rows were grouped by object first or not; and
    ``objects`` says which it was."""
    from transmogrifai_tpu.impl.feature import vectorizers
    vals, mask, objects = OBJECT_CASES[name]
    m = np.ones(len(vals), dtype=bool) if mask is None else mask
    before = vals.copy()
    codes, counts, path, met = vectorizers._factorize_valid(vals, m)
    want_codes, want_counts, want_path = _reference_factorize(vals, m)
    assert codes.dtype == np.intp and np.array_equal(codes, want_codes)
    assert list(counts.items()) == list(want_counts.items())
    assert all(type(k) is str and type(c) is int for k, c in counts.items())
    assert path == want_path
    assert met == objects
    assert all(a is b for a, b in zip(vals, before))     # nothing written


@pytest.mark.parametrize("codes", [
    [0], [0, 0, 0], [0, 1, 2, 3], [0, 0, 1, 0, 2, 1, 2, 3],
    [0] * 70000 + [1] + [0] * 70000 + [2, 1, 3],
    list(range(5)) * 30000 + [5]], ids=["one", "same", "rising", "mixed",
                                        "blocks", "last_row"])
def test_first_rows_are_where_each_code_first_appears(codes):
    from transmogrifai_tpu.impl.feature import vectorizers
    codes = np.asarray(codes, dtype=np.intp)
    levels, want = np.unique(codes, return_index=True)
    got = vectorizers._first_rows(codes, len(levels))
    assert got.dtype == np.intp and np.array_equal(got, want)


# -- the chip makes the dense block from positions (PR 35) --------------------

def _pivot_on(monkeypatch, path, stage, fit_table, table):
    """``stage`` fitted on ``fit_table`` and applied to ``table`` with the
    row constant set so that ``path`` is taken (None: as it is); (column,
    its spans)."""
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.observability import trace as ot
    if path is not None:
        monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS",
                            1 if path == "device" else 10 ** 12)
    model = stage.fit(fit_table)
    ot.reset()
    ot.enable_tracing(True)
    try:
        col = model.transform_column(table)
        spans = ot.tracer().finished()
    finally:
        ot.reset()
    return col, spans


def _meta_tuples(col):
    return [(c.parent_feature_name, c.parent_feature_type, c.grouping,
             c.indicator_value, c.descriptor_value, c.index)
            for c in col.metadata["vector_meta"].columns]


@pytest.mark.parametrize("name", [n for n in PIVOT_CASES if n not in (
    "empty_table",)])
def test_device_pivot_equals_the_host_pivot_bit_for_bit(monkeypatch, name):
    """Nulls tracked and not, levels outside the vocabulary, an all-null
    column, a ``top_k`` cut, ties, a column that is no ``str``: values AND
    ``vector_meta`` of the block the chip wrote from positions are the
    host block's."""
    import jax
    case = PIVOT_CASES[name]
    reps = 1 if name in ("one_row", "one_null_row") else 64
    feat = FeatureBuilder.PickList("c").extract_field().as_predictor()
    fit_col = _scalar_column(PickList, case["values"], case["masked"], reps)
    seen = (fit_col if case["transform"] is None
            else _scalar_column(PickList, case["transform"], (), reps))
    got = {}
    for path in ("host", "device"):
        st = OneHotVectorizer(top_k=case["top_k"],
                              min_support=case["min_support"] * reps,
                              track_nulls=case["track_nulls"])
        st.set_input(feat)
        col, spans = _pivot_on(
            monkeypatch, path, st, FeatureTable({"c": fit_col}, len(fit_col)),
            FeatureTable({"c": seen}, len(seen)))
        assert [s.attrs["path"] for s in spans
                if s.name == "onehot.expand"] == [path]
        got[path] = col
    assert isinstance(got["host"].values, np.ndarray)
    assert isinstance(got["device"].values, jax.Array)
    dev = np.asarray(got["device"].values)
    assert dev.dtype == np.float32 and dev.shape == got["host"].values.shape
    assert np.array_equal(dev, got["host"].values)
    assert _meta_tuples(got["device"]) == _meta_tuples(got["host"])
    if name == "ints":      # what the spans call "str_pass"
        assert {s.attrs["path"] for s in spans
                if s.name == "onehot.encode"} == {"str_pass"}


@pytest.mark.parametrize("rows_off", [-1, 0], ids=["just_under", "at"])
def test_the_row_constant_decides_the_pivots_path(rows_off):
    """Several columns at the constant itself (not patched): one row fewer
    and the host writes the block."""
    import jax
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.table import Column
    n = vectorizers._DEVICE_BLOCK_MIN_ROWS + rows_off
    rng = np.random.RandomState(5)
    feats, cols = [], {}
    for j, k in enumerate((3, 40)):
        lv = np.array([f"v{i}" for i in range(k)] + [None], dtype=object)
        vals = lv[rng.randint(0, k + 1, size=n)]
        cols[f"c{j}"] = Column(PickList, vals,
                               np.array([v is not None for v in vals]))
        feats.append(FeatureBuilder.PickList(f"c{j}").extract_field()
                     .as_predictor())
    table = FeatureTable(cols, n)
    st = OneHotVectorizer(top_k=8)
    st.set_input(*feats)
    model = st.fit(table)
    col = model.transform_column(table)
    assert isinstance(col.values, jax.Array if rows_off == 0 else np.ndarray)
    vals = np.asarray(col.values)
    assert vals.shape == (n, (3 + 2) + (8 + 2)) and vals.dtype == np.float32
    # every row has exactly one 1 in each column's block
    assert np.array_equal(vals[:, :5].sum(axis=1), np.ones(n, np.float32))
    assert np.array_equal(vals[:, 5:].sum(axis=1), np.ones(n, np.float32))
    # c0: vocabulary by count, then OTHER (never met), then the nulls
    c0 = cols["c0"]
    for i, v in enumerate(model.vocabs[0]):
        assert np.array_equal(vals[:, i] == 1.0, c0.values == v)
    assert not vals[:, 3].any()
    assert np.array_equal(vals[:, 4] == 1.0, ~c0.mask)


def test_a_multipicklist_input_keeps_the_host_path(monkeypatch):
    from transmogrifai_tpu.table import Column
    tags = FeatureBuilder.MultiPickList("t").extract_field().as_predictor()
    pick = FeatureBuilder.PickList("c").extract_field().as_predictor()
    sets = [{"a", "b"}, {"a"}, set(), None, {"c", "a"}] * 20
    picks = ["x", "y", None, "x", "z"] * 20
    table = FeatureTable({"t": Column.of_values(MultiPickList, sets),
                          "c": Column.of_values(PickList, picks)}, 100)
    got = {}
    for path in ("host", "device"):
        st = OneHotVectorizer(top_k=3, min_support=1)
        st.set_input(tags, pick)
        got[path], spans = _pivot_on(monkeypatch, path, st, table, table)
        # a model with a multi-valued column takes the host path whole
        assert [s.attrs["path"] for s in spans
                if s.name == "onehot.expand"] == ["host", "host"]
        assert isinstance(got[path].values, np.ndarray)
    assert np.array_equal(got["host"].values, got["device"].values)


def _categorical_workflow(df, reals=("x1",), picks=("c1", "c2")):
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector,
    )
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(k).extract_field().as_predictor()
             for k in reals]
    feats += [FeatureBuilder.PickList(k).extract_field().as_predictor()
              for k in picks]
    checked = transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        models=[("OpLogisticRegression", [{"regParam": 0.0135,
                                           "elasticNetParam": 0.0}])])
            .set_input(label, checked).get_output())
    return OpWorkflow().set_input_dataset(df).set_result_features(pred), pred


def test_a_score_over_the_row_constant_is_the_same_planned_and_eager(
        monkeypatch):
    import pandas as pd
    from transmogrifai_tpu import plan as plan_mod
    from transmogrifai_tpu.impl.feature import vectorizers
    rng = np.random.RandomState(3)
    n = 700
    df = pd.DataFrame({
        "x1": rng.randn(n),
        "c1": rng.choice(["a", "b", "c", None], size=n),
        "c2": rng.choice(["u", "v"], size=n)})
    df["y"] = ((df.x1 + (df.c1 == "a") - (df.c2 == "u")) > 0).astype(float)
    scores = {}
    for path, rows in (("host", 10 ** 12), ("device", 256)):
        monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", rows)
        wf, pred = _categorical_workflow(df)
        model = wf.train()
        planned = np.asarray(model.score(df=df)[pred.name].values)
        plan_mod.enable_planning(False)
        try:
            eager = np.asarray(model.score(df=df)[pred.name].values)
        finally:
            plan_mod.enable_planning(None)
        assert np.array_equal(planned, eager), path
        scores[path] = planned
    # and the chip's block trains and scores to the host block's bits
    assert np.array_equal(scores["host"], scores["device"])


def test_the_combiner_joins_on_the_chip_and_reads_nothing_back(monkeypatch):
    """Mixed inputs: a device block, a host matrix, a host vector. The
    result is ``np.concatenate`` of their host values; only the host
    inputs' bytes go up; no device input is read to the host."""
    import jax
    import jax.numpy as jnp
    from jax._src.array import ArrayImpl
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.observability import metrics as om, trace as ot
    from transmogrifai_tpu.table import Column
    from transmogrifai_tpu.types import OPVector
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 64)
    n = 100
    rng = np.random.RandomState(2)
    host = {"a": rng.rand(n, 5).astype(np.float32),
            "b": rng.rand(n, 3).astype(np.float32),
            "c": rng.rand(n).astype(np.float32)}
    feats = [FeatureBuilder.OPVector(k).extract_field().as_predictor()
             for k in ("a", "b")]
    feats.append(FeatureBuilder.Real("c").extract_field().as_predictor())
    table = FeatureTable({
        "a": Column(OPVector, jnp.asarray(host["a"]), None),
        "b": Column(OPVector, host["b"], None),
        "c": Column(Real, host["c"], None)}, n)
    comb = VectorsCombiner()
    comb.set_input(*feats)
    om.reset()
    om.enable_metrics(True)
    ot.reset()
    ot.enable_tracing(True)

    def read_back(self, *a, **kw):
        raise AssertionError("a device array was read to the host")
    real = ArrayImpl.__array__
    try:
        with ot.span("stage.transform", stage="VectorsCombiner") as sp:
            monkeypatch.setattr(ArrayImpl, "__array__", read_back)
            col = comb.transform_column(table)
            monkeypatch.setattr(ArrayImpl, "__array__", real)
        moved = om.registry().snapshot()["tg_transfer_bytes_total"]
    finally:
        monkeypatch.setattr(ArrayImpl, "__array__", real)
        om.reset()
        ot.reset()
    assert moved == {"direction=h2d": float(n * 3 * 4 + n * 4)}
    assert sp.attrs == {"stage": "VectorsCombiner", "deviceInputs": 1,
                        "hostInputs": 2, "h2dBytes": n * 3 * 4 + n * 4}
    assert isinstance(col.values, jax.Array)
    assert np.array_equal(np.asarray(col.values), np.concatenate(
        [host["a"], host["b"], host["c"][:, None]], axis=1))
    assert [c.parent_feature_name
            for c in col.metadata["vector_meta"].columns] == \
        ["a"] * 5 + ["b"] * 3 + ["c"]
    # one device input alone is handed on as it is
    one = VectorsCombiner()
    one.set_input(feats[0])
    assert one.transform_column(table).values is table["a"].values


def test_the_pivot_sends_positions_and_nothing_else(monkeypatch):
    """``tg_transfer_bytes_total`` of a device-path pivot: four bytes a row
    and column, against the block's (k + 2) x 4."""
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.observability import metrics as om
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 64)
    table = FeatureTable.from_columns({
        "c": (PickList, ["a", "b", None, "a"] * 50),
        "d": (PickList, ["p", "q", "r", "s"] * 50)})
    st = OneHotVectorizer(min_support=1)
    st.set_input(*[FeatureBuilder.PickList(k).extract_field().as_predictor()
                   for k in ("c", "d")])
    model = st.fit(table)
    om.reset()
    om.enable_metrics(True)
    try:
        col = model.transform_column(table)
        moved = om.registry().snapshot()["tg_transfer_bytes_total"]
    finally:
        om.reset()
    assert moved == {"direction=h2d": 200 * 2 * 4.0}
    assert col.values.shape == (200, 4 + 6)


# -- the chip fills the Real block from column uploads (PR 40) ----------------

def _real_case(stage, track_nulls, nulls, n=96, seed=11):
    """A stage of three inputs and their table: float32, float64 and int64
    storage (an Integral stage: int64, int32, int64), every column masked
    where ``nulls``, else one column without a mask object at all."""
    from transmogrifai_tpu.table import Column
    rng = np.random.RandomState(seed)
    integral = stage == "integral"
    ftype = Integral if integral else Real
    dtypes = ((np.int64, np.int32, np.int64) if integral
              else (np.float32, np.float64, np.int64))
    cols, feats = {}, []
    for j, dt in enumerate(dtypes):
        vals = (rng.randint(-5, 6, n) if np.issubdtype(dt, np.integer)
                else rng.randn(n) * 1e3 + 7).astype(dt)
        if nulls:
            mask = rng.rand(n) > 0.3
            if j == 2:
                mask[:] = False               # an all-null column
        else:
            mask = None if j == 0 else np.ones(n, bool)
        cols[f"x{j}"] = Column(ftype, vals, mask)
        feats.append(getattr(FeatureBuilder, ftype.__name__)(f"x{j}")
                     .extract_field().as_predictor())
    st = {"real_mean": lambda: RealVectorizer(track_nulls=track_nulls),
          "real_constant": lambda: RealVectorizer(
              fill_with_mean=False, fill_value=-2.5, track_nulls=track_nulls),
          "integral": lambda: IntegralVectorizer(track_nulls=track_nulls),
          }[stage]()
    st.set_input(*feats)
    return st, FeatureTable(cols, n)


def _fill_paths(spans):
    return [(s.name, s.attrs["path"]) for s in spans
            if s.name.startswith("realvec.")]


@pytest.mark.parametrize("nulls", [True, False], ids=["nulls", "no_nulls"])
@pytest.mark.parametrize("track_nulls", [True, False],
                         ids=["tracked", "untracked"])
@pytest.mark.parametrize("stage", ["real_mean", "real_constant", "integral"])
def test_device_real_block_equals_the_host_block_bit_for_bit(
        monkeypatch, stage, track_nulls, nulls):
    """Values AND ``vector_meta`` of the block the chip filled from column
    uploads are the host block's: nulls and none, tracked and not, mean,
    constant and mode fills, float64 and integer storage."""
    import jax
    got = {}
    for path in ("host", "device"):
        st, table = _real_case(stage, track_nulls, nulls)
        got[path], spans = _pivot_on(monkeypatch, path, st, table, table)
        assert _fill_paths(spans) == [("realvec.fill", path),
                                      ("realvec.stack", path)]
    assert isinstance(got["host"].values, np.ndarray)
    assert isinstance(got["device"].values, jax.Array)
    dev = np.asarray(got["device"].values)
    assert dev.dtype == np.float32
    assert dev.shape == got["host"].values.shape == (
        96, 3 * (2 if track_nulls else 1))
    assert np.array_equal(dev.view(np.uint32),
                          got["host"].values.view(np.uint32))
    assert _meta_tuples(got["device"]) == _meta_tuples(got["host"])


@pytest.mark.parametrize("rows_off", [-1, 0], ids=["just_under", "at"])
def test_the_row_constant_decides_the_real_blocks_path(rows_off):
    """At the constant itself (not patched) the chip fills the block; one
    row fewer and the host does. Both spans say which."""
    import jax
    from transmogrifai_tpu.impl.feature import vectorizers
    n = vectorizers._DEVICE_BLOCK_MIN_ROWS + rows_off
    st, table = _real_case("real_mean", True, True, n=n)
    col, spans = _pivot_on(None, None, st, table, table)
    path = "device" if rows_off == 0 else "host"
    assert _fill_paths(spans) == [("realvec.fill", path),
                                  ("realvec.stack", path)]
    assert isinstance(col.values, jax.Array if rows_off == 0 else np.ndarray)
    vals = np.asarray(col.values)
    assert vals.shape == (n, 6) and vals.dtype == np.float32
    x0 = table["x0"]
    assert np.array_equal(vals[:, 0], np.where(
        x0.mask, x0.values, np.float32(x0.values[x0.mask].mean(
            dtype=np.float64))))
    assert np.array_equal(vals[:, 1] == 1.0, ~x0.mask)


def test_the_real_block_sends_columns_and_masks_and_nothing_else(monkeypatch):
    """``tg_transfer_bytes_total`` of a device-path fill: four bytes a row
    and column and one a row and mask, against the block's eight a row and
    column; a second fit with other fills finds the program."""
    from benchmark.harness import COMPILE_EVENT, Monitor
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.observability import metrics as om
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 64)
    n = 200
    st, table = _real_case("real_mean", True, False, n=n)    # two masks
    model = st.fit(table)
    om.reset()
    om.enable_metrics(True)
    try:
        col = model.transform_column(table)
        moved = om.registry().snapshot()["tg_transfer_bytes_total"]
    finally:
        om.reset()
    assert moved == {"direction=h2d": n * 3 * 4.0 + n * 2 * 1.0}
    assert col.values.shape == (n, 6)
    # the fills are an argument of the program, not constants inside it
    other, _ = _real_case("real_constant", True, False, n=n)
    again = other.fit(table)
    assert again.fills != model.fills
    monitor = Monitor().install()
    monitor.phase = "second"
    second = again.transform_column(table)
    monitor.phase = "after"
    assert monitor.count("second", COMPILE_EVENT) == 0
    assert np.array_equal(np.asarray(second.values),
                          np.asarray(col.values))   # no null: no fill shows


@pytest.mark.parametrize("n", [4000, 4001], ids=["divides", "odd"])
def test_the_real_block_is_born_sharded_under_a_mesh(monkeypatch, n):
    """Under ``data=4`` the columns go up as shards and the block's rows are
    sharded; a row count the data axis does not divide stays on one
    device. The bits are the host path's either way."""
    import jax
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.parallel.mesh import MeshSpec, make_mesh
    from transmogrifai_tpu.parallel.sharded import row_sharding
    st, table = _real_case("real_constant", True, True, n=n)
    want, _ = _pivot_on(monkeypatch, "host", st, table, table)
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 1000)
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    st, table = _real_case("real_constant", True, True, n=n)
    model = st.set_mesh(mesh).fit(table)
    assert model.mesh is mesh
    went_up = []
    real = vectorizers._upload

    def upload(host, mesh, site):
        went_up.append(real(host, mesh, site))
        return went_up[-1]
    monkeypatch.setattr(vectorizers, "_upload", upload)
    col = model.transform_column(table)
    assert sorted(a.shape for a in went_up) == [(n,)] * 6
    if n % 4 == 0:
        assert col.values.sharding.is_equivalent_to(row_sharding(mesh, 2), 2)
        for a in went_up:
            assert a.sharding.is_equivalent_to(row_sharding(mesh, 1), 1)
    else:
        assert len(col.values.sharding.device_set) == 1
    assert np.array_equal(np.asarray(col.values), want.values)


def test_a_real_block_reaches_the_selector_without_a_host_matrix(monkeypatch):
    """Real vectorizer -> SanityChecker -> selector: with reals alone
    ``transmogrify`` hands the one vector on without a combiner, so the
    Real block goes to the checker as it is (the ``train-higgs`` shape).
    Over the row constant the checker, its model and the selector are
    handed device arrays, no matrix of rows x derived columns is read back
    to the host, and the scores are the host path's bits."""
    import jax
    import pandas as pd
    from jax._src.array import ArrayImpl
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.impl.preparators import sanity_checker as sc
    from transmogrifai_tpu.impl.selector import model_selector as ms
    rng = np.random.RandomState(4)
    n = 700
    df = pd.DataFrame({"x1": rng.randn(n), "x2": rng.randn(n),
                       "x3": rng.randn(n)})
    df.loc[rng.rand(n) < 0.2, "x2"] = np.nan
    df["y"] = ((df.x1 - df.x3) > 0).astype(float)
    seen = []

    def spying(cls, method):
        real = getattr(cls, method)

        def spy(self, table):
            seen.append((cls.__name__, method, type(
                table[self.input_features[1].name].values)))
            return real(self, table)
        monkeypatch.setattr(cls, method, spy)
    spying(sc.SanityChecker, "fit_queued")
    spying(sc.SanityCheckerModel, "transform_column")
    spying(ms.ModelSelector, "fit")
    spying(ms.SelectedModel, "transform_column")
    real_read = ArrayImpl.__array__

    def read_back(self, *a, **kw):
        assert not (self.ndim == 2 and self.shape[0] == n
                    and self.shape[1] >= 3), "a matrix came back to the host"
        return real_read(self, *a, **kw)
    scores = {}
    for path, rows in (("host", 10 ** 12), ("device", 256)):
        monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", rows)
        del seen[:]
        wf, pred = _categorical_workflow(df, ("x1", "x2", "x3"), ())
        if path == "device":
            monkeypatch.setattr(ArrayImpl, "__array__", read_back)
        try:
            model = wf.train()
        finally:
            monkeypatch.setattr(ArrayImpl, "__array__", real_read)
        kinds = {np.ndarray if path == "host" else jax.Array}
        assert len(seen) == 4 and all(
            issubclass(t, tuple(kinds)) for _, _, t in seen), seen
        assert [type(s).__name__ for s in model.stages[:2]] == [
            "RealVectorizerModel", "SanityCheckerModel"]
        scores[path] = np.asarray(model.score(df=df)[pred.name].values)
    assert np.array_equal(scores["host"], scores["device"])
