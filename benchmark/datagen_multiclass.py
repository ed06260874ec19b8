"""Rows from ``--seed`` for a multiclass configuration (``label_rule.kind``
``class_counts``): the label is drawn first, then every column from its own
distribution shifted by the row's class. Reuses ``datagen.py``'s level
names, skews, seed streams and ``Generated``.

The seed draws the rows only. How many rows each class has is the file's
(``label_rule.source_counts`` scaled to the rows asked for, each class at
least ``min_rows``), so every seed gives the same classes, the same derived
width, the same compiled shapes and the same work; the seed says WHICH rows
a class gets. Per-class tilts and shifts are drawn once from
``label_rule.rule_seed``, never from ``--seed``.

Column kinds:

* ``PickList``: ``levels`` with ``skew`` (``datagen.level_probs``); class c
  draws from ``p(level) * exp(class_tilt * t[c, level])`` renormalised,
  ``t`` standard normal from the rule seed.
* ``Real``: a mixture of point masses (``atoms``, ``atom_probs``: the many
  exact zeros of a byte count, the 0 and 1 of a rate) and a ``body``
  (``normal``, ``lognormal`` or ``uniform``, datagen's kinds, with ``clip``,
  ``round`` and ``decimals``). Class c tilts the mixture's weights by
  ``exp(class_tilt * t[c, part])`` and moves a normal or lognormal body by
  ``class_shift * s[c]`` deviations. ``constant`` is one value on every row.

Everything is bulk numpy, per class and column: no per-row python.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from .datagen import (_LABEL_STREAM, Generated, _rng, level_names,
                      level_probs)


def class_counts(rule: Dict[str, Any], rows: int) -> np.ndarray:
    """Rows of each class among ``rows``: the source's shares, each class at
    least ``min_rows``, the largest class taking up the rounding."""
    src = np.asarray(rule["source_counts"], dtype=np.float64)
    counts = np.maximum(np.round(src / src.sum() * int(rows)),
                        int(rule.get("min_rows", 1))).astype(np.int64)
    counts[int(np.argmax(src))] += int(rows) - int(counts.sum())
    if counts.min() < 1:
        raise ValueError(f"{rows} rows cannot hold {len(counts)} classes")
    return counts


def _tilted(base: np.ndarray, tilt: float, t: np.ndarray) -> np.ndarray:
    """(C, k) per-class cumulative distribution of ``base`` (k,) tilted by
    ``exp(tilt * t)``, ``t`` (C, k)."""
    p = base[None, :] * np.exp(float(tilt) * t)
    cdf = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
    cdf[:, -1] = 1.0
    return cdf


def _draw_parts(cdf: np.ndarray, u: np.ndarray, by_class: List[np.ndarray]
                ) -> np.ndarray:
    out = np.zeros(len(u), dtype=np.int32)
    for c, idx in enumerate(by_class):
        out[idx] = np.searchsorted(cdf[c], u[idx], side="right")
    return np.minimum(out, cdf.shape[1] - 1)


def _body(d: Dict[str, Any], rng, n: int, shift: np.ndarray) -> np.ndarray:
    """``n`` draws of a body; ``shift`` (n,) in deviations."""
    f32 = np.float32
    kind = d["kind"]
    if kind == "uniform":
        v = rng.uniform(d["lo"], d["hi"], n).astype(f32)
    elif kind in ("normal", "lognormal"):
        z = rng.standard_normal(n, dtype=f32) + shift.astype(f32)
        v = f32(d["mu"]) + f32(d["sigma"]) * z
        if kind == "lognormal":
            v = np.exp(v, dtype=f32)
    else:
        raise ValueError(f"unknown body kind {kind!r}")
    if "clip" in d:
        v = np.clip(v, f32(d["clip"][0]), f32(d["clip"][1]))
    if d.get("round"):
        v = np.round(v)
    if "decimals" in d:
        v = np.round(v, int(d["decimals"]))
    return v.astype(f32)


def _draw_real(col, rng, rule_rng, y, by_class, C) -> np.ndarray:
    d = col["dist"]
    n = len(y)
    if d["kind"] == "constant":
        return np.full(n, np.float32(d["value"]))
    atoms = np.asarray(d.get("atoms", []), dtype=np.float32)
    probs = np.asarray(d.get("atom_probs", []), dtype=np.float64)
    base = np.r_[probs, 1.0 - probs.sum()]
    t = rule_rng.standard_normal((C, len(base)))
    s = rule_rng.standard_normal(C)
    part = _draw_parts(_tilted(base, d.get("class_tilt", 0.0), t),
                       rng.random(n), by_class)
    in_body = part == len(atoms)
    out = np.zeros(n, dtype=np.float32)
    if len(atoms):
        out[~in_body] = atoms[part[~in_body]]
    out[in_body] = _body(d, rng, int(in_body.sum()),
                         float(d.get("class_shift", 0.0)) * s[y[in_body]])
    return out


def generate(config: Dict[str, Any], seed: int, rows: int) -> Generated:
    """``rows`` rows of ``config``'s schema from ``seed``; the label is the
    class index 0..C-1 (float32), in the order of ``source_counts``."""
    cols: List[Dict[str, Any]] = config["columns"]
    rule = config["label_rule"]
    if rule["kind"] != "class_counts":
        raise ValueError(f"unknown label rule {rule['kind']!r}")
    n = int(rows)
    counts = class_counts(rule, n)
    C = len(counts)
    y = np.repeat(np.arange(C, dtype=np.int32), counts)
    _rng(seed, _LABEL_STREAM).shuffle(y)
    by_class = [np.nonzero(y == c)[0] for c in range(C)]

    out: Dict[str, np.ndarray] = {}
    types: Dict[str, str] = {}
    for i, col in enumerate(cols):
        rng = _rng(seed, i)
        rule_rng = np.random.default_rng([int(rule["rule_seed"]), i])
        types[col["name"]] = col["type"]
        if col["type"] == "PickList":
            t = rule_rng.standard_normal((C, int(col["levels"])))
            codes = _draw_parts(
                _tilted(level_probs(col), col.get("class_tilt", 0.0), t),
                rng.random(n), by_class)
            out[col["name"]] = level_names(col)[codes]
        elif col["type"] == "Real":
            out[col["name"]] = _draw_real(col, rng, rule_rng, y, by_class, C)
        else:
            raise ValueError(f"unknown column type {col['type']!r}")
    return Generated(out, types, y.astype(np.float32), None)
