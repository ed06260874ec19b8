"""Rows from ``--seed`` for a regression configuration (``label_rule.kind``
``fare``): the columns are drawn first, the label is a rule over them with
multiplicative noise. Reuses ``datagen.py``'s level names, skews, seed
streams and ``Generated``.

The seed draws the rows only. Level shares, mixtures, the timestamp's
rhythms and the rule's strength are the file's; the rule's per-level effects
are drawn once from ``label_rule.rule_seed``, never from ``--seed``. So every
seed gives the same derived width, the same compiled shapes and the same
work.

Column kinds:

* ``PickList``: ``levels`` with ``skew`` (``datagen.level_probs``, or
  ``{"kind": "stated", "p": [...]}`` for the source's own shares) and
  optionally the levels' own ``names``.
* ``DateTime``: epoch milliseconds (int64) from ``start_ms`` over ``days``
  days; the day of the week is weighted by ``weekday_weights`` (Monday
  first), the minute of the day is drawn as ``datagen``'s ``clock`` does
  (``peak_minutes``, ``sigma_minutes``, wrapped round midnight).
* ``Integral``: ``values`` with ``probs`` (int32).
* ``Real``: ``lognormal`` (``mu``, ``sigma``, ``clip``, ``decimals``) or a
  ``mixture`` of normals (``parts``: ``p``, ``mu``, ``sigma``), each with
  point masses (``atoms``, ``atom_probs``). Columns that name the same
  ``group`` draw the part once between them (a pick-up's longitude and
  latitude lie in the same cluster, and are the source's exact zero
  together).

The label (``label_rule.kind`` ``fare``): ``flag_drop + per_unit * column``
plus per-level effects (drawn from ``rule_seed``, the ``anchor`` level's
taken off so that the commonest level carries none), plus
``amplitude * sin(2 pi (hour - phase) / 24)`` of a timestamp's hour, all
times ``exp(noise_sigma * z)``, kept at or above ``floor``; rows of one
level of one column (``flat``) get its ``value`` exactly, the flat fare's
point mass. Everything is bulk numpy: no per-row
python.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from .datagen import (_LABEL_STREAM, Generated, _rng, level_names,
                      level_probs)

_GROUP_STREAM = 2_000_003
_HOUR_MS = 3_600_000
_DAY_MS = 24 * _HOUR_MS


def _probs(col: Dict[str, Any]) -> np.ndarray:
    skew = col.get("skew", {"kind": "uniform"})
    if skew["kind"] != "stated":
        return level_probs(col)
    p = np.asarray(skew["p"], dtype=np.float64)
    if len(p) != int(col["levels"]):
        raise ValueError(f"{col['name']}: {len(p)} shares for "
                         f"{col['levels']} levels")
    return p / p.sum()


def _names(col: Dict[str, Any]) -> np.ndarray:
    if "names" in col:
        return np.array([str(v) for v in col["names"]], dtype=object)
    return level_names(col)


def _pick(p: np.ndarray, u: np.ndarray) -> np.ndarray:
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    return np.searchsorted(cdf, u, side="right").astype(np.int32)


def _draw_datetime(col, rng, n) -> np.ndarray:
    d = col["dist"]
    days = int(d["days"])
    first_weekday = (int(d["start_ms"]) // _DAY_MS + 3) % 7   # Monday = 0
    w = np.asarray(d["weekday_weights"], dtype=np.float64)
    day = _pick(w[(first_weekday + np.arange(days)) % 7], rng.random(n))
    peaks = np.asarray(d["peak_minutes"], dtype=np.float64)
    minute = (peaks[rng.integers(0, len(peaks), n)]
              + float(d["sigma_minutes"]) * rng.standard_normal(n)) % 1440.0
    ms = minute.astype(np.int64) * 60_000 + rng.integers(0, 60_000, n)
    return int(d["start_ms"]) + day.astype(np.int64) * _DAY_MS + ms


def _draw_real(col, rng, part_u, n) -> np.ndarray:
    d = col["dist"]
    f32 = np.float32
    atoms = np.asarray(d.get("atoms", []), dtype=f32)
    probs = np.asarray(d.get("atom_probs", []), dtype=np.float64)
    parts = d.get("parts", [{"p": 1.0}])
    share = np.asarray([float(p["p"]) for p in parts])
    which = _pick(np.r_[probs, (1.0 - probs.sum()) * share / share.sum()],
                  part_u)
    body = which >= len(atoms)
    k = which[body] - len(atoms)
    z = rng.standard_normal(int(body.sum()))
    if d["kind"] == "lognormal":
        v = np.exp(float(d["mu"]) + float(d["sigma"]) * z)
    elif d["kind"] == "mixture":
        v = (np.asarray([float(p["mu"]) for p in parts])[k]
             + np.asarray([float(p["sigma"]) for p in parts])[k] * z)
    else:
        raise ValueError(f"unknown dist kind {d['kind']!r}")
    if "clip" in d:
        v = np.clip(v, float(d["clip"][0]), float(d["clip"][1]))
    if "decimals" in d:
        v = np.round(v, int(d["decimals"]))
    out = np.zeros(n, dtype=f32)
    out[~body] = atoms[which[~body]]
    out[body] = v.astype(f32)
    return out


def _fare(rule, cols, codes, out, seed) -> np.ndarray:
    by_name = {c["name"]: (i, c) for i, c in enumerate(cols)}
    n = len(next(iter(out.values())))
    f = np.full(n, float(rule["flag_drop"]), dtype=np.float64)
    for term in rule["terms"]:
        i, col = by_name[term["column"]]
        if term["kind"] == "per_unit":
            f += float(term["coef"]) * out[col["name"]].astype(np.float64)
        elif term["kind"] == "level_effects":
            eff = np.random.default_rng([int(rule["rule_seed"]), i]).normal(
                0.0, float(term["scale"]), int(col["levels"]))
            f += (eff - eff[int(term.get("anchor", 0))])[codes[col["name"]]]
        elif term["kind"] == "hour_of_day":
            hour = (out[col["name"]] // _HOUR_MS) % 24
            f += float(term["amplitude"]) * np.sin(
                2.0 * np.pi * (hour - float(term["phase"])) / 24.0)
        else:
            raise ValueError(f"unknown term kind {term['kind']!r}")
    z = _rng(seed, _LABEL_STREAM).standard_normal(n)
    f = np.maximum(f * np.exp(float(rule["noise_sigma"]) * z),
                   float(rule["floor"]))
    flat = rule.get("flat")
    if flat:
        f[codes[flat["column"]] == int(flat["level"])] = float(flat["value"])
    return np.round(f, 2).astype(np.float32)


def generate(config: Dict[str, Any], seed: int, rows: int) -> Generated:
    """``rows`` rows of ``config``'s schema from ``seed``; the label is the
    fare (float32, positive)."""
    cols: List[Dict[str, Any]] = config["columns"]
    rule = config["label_rule"]
    if rule["kind"] != "fare":
        raise ValueError(f"unknown label rule {rule['kind']!r}")
    n = int(rows)
    out: Dict[str, np.ndarray] = {}
    types: Dict[str, str] = {}
    codes: Dict[str, np.ndarray] = {}
    group_u: Dict[str, np.ndarray] = {}
    for i, col in enumerate(cols):
        rng = _rng(seed, i)
        name = col["name"]
        types[name] = col["type"]
        if col["type"] == "PickList":
            codes[name] = _pick(_probs(col), rng.random(n))
            out[name] = _names(col)[codes[name]]
        elif col["type"] == "DateTime":
            out[name] = _draw_datetime(col, rng, n)
        elif col["type"] == "Integral":
            d = col["dist"]
            out[name] = np.asarray(d["values"], dtype=np.int32)[
                _pick(np.asarray(d["probs"], dtype=np.float64),
                      rng.random(n))]
        elif col["type"] == "Real":
            group = col.get("group")
            if group is None:
                u = rng.random(n)
            else:
                if group not in group_u:
                    group_u[group] = _rng(seed, _GROUP_STREAM + i).random(n)
                u = group_u[group]
            out[name] = _draw_real(col, rng, u, n)
        else:
            raise ValueError(f"unknown column type {col['type']!r}")
    return Generated(out, types, _fare(rule, cols, codes, out, seed), None)
