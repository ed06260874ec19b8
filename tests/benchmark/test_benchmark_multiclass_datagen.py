"""The multiclass generator on the ``kddcup99-1m`` configuration: the class
counts are the file's and exact at every seed (the seed says which rows),
the floor of 16 rows holds for the twelve rarest classes, and nothing that
decides a compiled shape (levels, atoms, derived slots) moves with the
seed."""
import json
import os

import numpy as np
import pytest

from benchmark import datagen, datagen_multiclass, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kddcup99-1m.json")) as f:
        return json.load(f)


def test_the_schema_is_the_sources(cfg):
    kinds = [c["type"] for c in cfg["columns"]]
    assert len(kinds) == 41
    assert kinds.count("PickList") == 7 and kinds.count("Real") == 34
    levels = {c["name"]: c["levels"] for c in cfg["columns"]
              if c["type"] == "PickList"}
    assert levels == {"protocol_type": 3, "service": 70, "flag": 11,
                      "land": 2, "logged_in": 2, "is_host_login": 2,
                      "is_guest_login": 2}
    rule = cfg["label_rule"]
    assert sum(rule["source_counts"]) == cfg["source_rows"] == 4898431
    assert len(rule["source_counts"]) == len(rule["classes"]) == 23
    assert sum(rule["source_counts"][:3]) / cfg["source_rows"] > 0.99
    assert cfg["rows"] + cfg["holdout_rows"] == 1100000


def test_class_counts_are_exact_and_floored(cfg):
    rule = cfg["label_rule"]
    counts = datagen_multiclass.class_counts(rule, 1100000)
    assert counts.sum() == 1100000
    assert (counts == 16).sum() == 12 and counts.min() == 16
    share = np.array(rule["source_counts"]) / sum(rule["source_counts"])
    big = counts > 16
    # the floor's 192 rows (less the 43 that the shares gave those classes)
    # come out of the largest class
    assert np.abs(counts[big][1:] - share[big][1:] * 1100000).max() <= 0.5
    assert 0 < share[0] * 1100000 - counts[0] < 192
    with pytest.raises(ValueError):
        datagen_multiclass.class_counts(dict(rule, min_rows=60000), 1100000)


@pytest.fixture(scope="module")
def drawn(cfg):
    n = 120000
    return {seed: datagen_multiclass.generate(cfg, seed, n)
            for seed in (7, 2 ** 31 + 99)}, n


def test_the_seed_says_which_rows_not_how_many(cfg, drawn):
    gens, n = drawn
    a, b = gens[7], gens[2 ** 31 + 99]
    want = datagen_multiclass.class_counts(cfg["label_rule"], n)
    for g in (a, b):
        assert np.array_equal(np.bincount(g.label.astype(int)), want)
        assert g.label.dtype == np.float32 and g.true_prob is None
    assert not np.array_equal(a.label, b.label)
    again = datagen_multiclass.generate(cfg, 7, n)
    assert np.array_equal(a.label, again.label)
    for k in a.columns:
        assert np.array_equal(a.columns[k], again.columns[k])


def test_nothing_that_decides_a_shape_moves_with_the_seed(cfg, drawn):
    gens, n = drawn
    for col in cfg["columns"]:
        name = col["name"]
        seen = [set(np.unique(g.columns[name]).tolist())
                for g in gens.values()]
        if col["type"] == "PickList":
            names = set(datagen.level_names(col))
            assert all(s <= names for s in seen)
            if col["levels"] <= 11:          # every level at every seed
                assert all(s == names for s in seen), name
        elif col["dist"]["kind"] == "constant":
            assert seen[0] == seen[1] == {col["dist"]["value"]}
        else:
            d = col["dist"]
            lo, hi = d.get("clip", (d.get("lo", 0.0), d.get("hi", 1.0)))
            atoms = d.get("atoms", [])
            for g in gens.values():
                v = g.columns[name]
                assert v.dtype == np.float32 and np.isfinite(v).all()
                assert v.min() >= min([lo] + atoms)
                assert v.max() <= max([hi] + atoms)
    # the column's class signal is the rule seed's: the same at every seed
    for g in gens.values():
        y = g.label.astype(int)
        assert abs(g.columns["count"][y == 0].mean()
                   - gens[7].columns["count"][gens[7].label == 0].mean()) < 5


def test_the_reference_builds_the_same_matrix_from_every_seed(cfg, drawn):
    """One-hot slots as ``transmogrify`` lays them out for this schema (top
    20 levels of a PickList + OTHER + null, a value and a null indicator per
    Real): the same count at every seed."""
    gens, _ = drawn
    widths = []
    for g in gens.values():
        slots = []
        for col in cfg["columns"]:
            if col["type"] == "PickList":
                u, c = np.unique(g.columns[col["name"]], return_counts=True)
                top = u[np.argsort(-c, kind="stable")][:20]
                slots += [(col["name"], v) for v in top]
                slots += [(col["name"], reference.OTHER_INDICATOR),
                          (col["name"], reference.NULL_INDICATOR)]
            else:
                slots += [(col["name"], None),
                          (col["name"], reference.NULL_INDICATOR)]
        X = reference.feature_matrix(
            {k: v[:2000] for k, v in g.columns.items()}, g.types, slots,
            slots)
        widths.append(X.shape[1])
        assert np.isfinite(X).all()
    assert widths == [124, 124]
