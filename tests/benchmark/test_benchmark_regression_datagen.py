"""The regression generator on the ``nyctaxi-2015-01`` configuration: the
schema is the source's, nothing that decides a compiled shape moves with the
seed, the label is positive and heavy-tailed with the flat fare's mass, and
the reference rebuilds the program's own feature vector (timestamp periods,
integer column, one-hot, reals) from raw rows to the last bit."""
import json
import os

import numpy as np
import pytest

from benchmark import datagen_regression, workflows
from benchmark import reference_regression as rr
from benchmark.kinds import train_regression_closed_loop as kind

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SOURCE_COLUMNS = [
    "VendorID", "tpep_pickup_datetime", "tpep_dropoff_datetime",
    "passenger_count", "trip_distance", "pickup_longitude",
    "pickup_latitude", "RateCodeID", "store_and_fwd_flag",
    "dropoff_longitude", "dropoff_latitude", "payment_type", "fare_amount",
    "extra", "mta_tax", "tip_amount", "tolls_amount",
    "improvement_surcharge", "total_amount"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nyctaxi-2015-01.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def drawn(cfg):
    n = 60000
    return {seed: datagen_regression.generate(cfg, seed, n)
            for seed in (7, 2 ** 31 + 99)}, n


def test_the_schema_is_the_sources(cfg):
    names = [c["name"] for c in cfg["columns"]]
    assert set(names) | set(cfg["label_parts"]) | {cfg["label"]} \
        == set(SOURCE_COLUMNS) and len(SOURCE_COLUMNS) == 19
    assert [n for n in SOURCE_COLUMNS if n in names] == names
    kinds = {c["name"]: c["type"] for c in cfg["columns"]}
    assert kinds == {
        "VendorID": "PickList", "tpep_pickup_datetime": "DateTime",
        "passenger_count": "Integral", "trip_distance": "Real",
        "pickup_longitude": "Real", "pickup_latitude": "Real",
        "RateCodeID": "PickList", "store_and_fwd_flag": "PickList",
        "dropoff_longitude": "Real", "dropoff_latitude": "Real",
        "payment_type": "PickList"}
    levels = {c["name"]: c["levels"] for c in cfg["columns"]
              if c["type"] == "PickList"}
    assert levels == {"VendorID": 2, "RateCodeID": 7,
                      "store_and_fwd_flag": 2, "payment_type": 5}
    assert cfg["problem"] == "regression" and cfg["source_rows"] == 12748986
    assert cfg["reduced"] == ["rows", "label_rule"]
    assert cfg["holdout_rows"] * 10 == cfg["rows"] < cfg["source_rows"]
    assert cfg["rows"] % 1000000 == 0
    assert cfg["workflow"]["selector"]["models"] == "stock"
    assert cfg["workflow"]["expected_fits"] == 150
    for key in ("why", "rows", "label", "marginals", "nulls",
                "derived_width", "device_bytes"):
        assert len(cfg["assumed"][key]) > 40, key
    assert len(cfg["check"]["reasons"]) > 400


def test_the_seed_says_which_rows_and_nothing_else(cfg, drawn):
    gens, n = drawn
    a, b = gens[7], gens[2 ** 31 + 99]
    again = datagen_regression.generate(cfg, 7, n)
    for k in a.columns:
        assert np.array_equal(a.columns[k], again.columns[k])
        assert not np.array_equal(a.columns[k], b.columns[k])
    assert np.array_equal(a.label, again.label)
    with pytest.raises(ValueError):
        datagen_regression.generate(cfg, -1, 10)
    for col in cfg["columns"]:
        seen = [set(np.unique(g.columns[col["name"]]).tolist())
                for g in gens.values()]
        if col["type"] == "PickList":        # every level at every seed
            assert seen[0] == seen[1] == {str(v) for v in col["names"]}
        elif col["type"] == "Integral":
            assert all(s <= set(col["dist"]["values"]) for s in seen)
            for g in gens.values():
                v = g.columns[col["name"]]
                assert v.dtype == np.int32 and (v == 1).mean() > 0.6


def test_the_timestamp_has_the_months_days_and_rhythms(cfg, drawn):
    gens, _ = drawn
    col = next(c for c in cfg["columns"] if c["type"] == "DateTime")
    for g in gens.values():
        ms = g.columns[col["name"]]
        assert ms.dtype == np.int64
        day = ms.astype("datetime64[ms]").astype("datetime64[D]")
        assert day.min() == np.datetime64("2015-01-01")
        assert day.max() == np.datetime64("2015-01-31")
        hour = rr.period_value(ms, "HourOfDay")
        share = np.bincount(hour, minlength=24) / len(ms)
        assert share[19] > 2 * share[4]          # the evening against 4 a.m.
        dow = rr.period_value(ms, "DayOfWeek")
        assert set(np.unique(dow)) == set(range(1, 8))


def test_the_label_is_a_fare(cfg, drawn):
    gens, _ = drawn
    flat = cfg["label_rule"]["flat"]
    for g in gens.values():
        y = g.label
        assert y.dtype == np.float32 and g.true_prob is None
        assert y.min() >= cfg["label_rule"]["floor"] and np.isfinite(y).all()
        assert 9 < y.mean() < 15 and y.max() > 100     # heavy tail
        at_flat = g.columns[flat["column"]] == "2"
        assert (y[at_flat] == 52.0).all() and 0.015 < at_flat.mean() < 0.03
        d = g.columns["trip_distance"]
        assert np.corrcoef(d[~at_flat], y[~at_flat])[0, 1] > 0.9
        assert (d == 0).mean() > 0.003
        for c in ("pickup_longitude", "pickup_latitude"):
            assert 0.01 < (g.columns[c] == 0).mean() < 0.03
        assert np.array_equal(g.columns["pickup_longitude"] == 0,
                              g.columns["pickup_latitude"] == 0)


def test_the_reference_rebuilds_the_programs_vector(cfg, drawn):
    """``transmogrify`` + ``sanity_check`` over 20 000 generated rows through
    the program, and the reference's matrix of the same rows from raw
    columns and the slots' descriptions: equal to the last bit, the
    timestamp's eight columns and the integer's among them."""
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.workflow import OpWorkflow
    gens, _ = drawn
    gen = gens[7].slice(0, 20000)
    table = workflows.table_of(gen, cfg["label"])
    label = FeatureBuilder.RealNN(cfg["label"]).extract_field().as_response()
    feats = [getattr(FeatureBuilder, c["type"])(c["name"]).extract_field()
             .as_predictor() for c in cfg["columns"]]
    vector = tg.transmogrify(feats)
    checked = vector.sanity_check(label)
    model = OpWorkflow().set_input_table(table).set_result_features(
        checked).train()
    out = model.score(table=table)
    full, kept = (kind.slots_of(out[f.name]) for f in (vector, checked))
    assert 38 <= len(full) <= 44     # 44 where every level has 10 rows
    assert sum(p == "tpep_pickup_datetime" for p, _, _ in full) == 8
    assert sum(p == "passenger_count" for p, _, _ in full) == 2
    assert 26 <= len(kept) <= 34
    X = rr.feature_matrix(gen.columns, gen.types, full, kept)
    got = np.asarray(out[checked.name].values, dtype=np.float32)
    assert got.shape == X.shape and np.array_equal(got, X)
    low = rr.to_bf16(X)
    assert np.abs(low - X).max() > 0.1


@pytest.mark.parametrize("period", ["HourOfDay", "DayOfWeek", "DayOfMonth",
                                    "DayOfYear"])
def test_calendar_values_against_the_standard_library(period):
    import datetime
    rng = np.random.default_rng(3)
    ms = rng.integers(0, 2_000_000_000_000, 500)
    got = rr.period_value(ms, period)
    for m, v in zip(ms.tolist(), got.tolist()):
        t = datetime.datetime.fromtimestamp(m / 1000.0, datetime.timezone.utc)
        want = {"HourOfDay": t.hour, "DayOfWeek": t.isoweekday(),
                "DayOfMonth": t.day,
                "DayOfYear": t.timetuple().tm_yday}[period]
        assert v == want
