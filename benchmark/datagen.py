"""Rows from ``--seed``: one general generator, driven by a configuration
file's ``columns`` and ``label_rule``.

The seed draws the rows only. Level counts, skews, distributions and the
label rule's coefficients are the configuration's, so every seed gives the
same derived width, the same compiled shapes and the same amount of work.
Everything is bulk numpy: no per-row python.

Two kinds of label rule:

* ``logistic``: columns are drawn first, the label is Bernoulli of a
  logistic rule over them (per-level effects drawn once from ``rule_seed``,
  linear terms over reals).
* ``latent_class``: the label is drawn first and shifts each column's
  distribution (``class_shift``, ``class_scale``, ``class_tilt``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

_LABEL_STREAM = 1_000_003


@dataclass
class Generated:
    """Raw columns by name (object arrays of str for ``PickList``, float32
    for ``Real``), the label, and the rule's own probability of a positive
    (None where the rule states none)."""
    columns: Dict[str, np.ndarray]
    types: Dict[str, str]
    label: np.ndarray
    true_prob: Optional[np.ndarray]

    def slice(self, lo: int, hi: int) -> "Generated":
        return Generated({k: v[lo:hi] for k, v in self.columns.items()},
                         dict(self.types), self.label[lo:hi],
                         None if self.true_prob is None
                         else self.true_prob[lo:hi])

    @property
    def rows(self) -> int:
        return int(self.label.shape[0])


def _rng(seed: int, stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must not be negative, got {seed}")
    return np.random.default_rng([int(seed), int(stream)])


def level_names(col: Dict[str, Any]) -> np.ndarray:
    return np.array([f"{col.get('prefix', 'L')}{i + 1}"
                     for i in range(int(col["levels"]))], dtype=object)


def level_probs(col: Dict[str, Any]) -> np.ndarray:
    skew = col.get("skew", {"kind": "uniform"})
    k = int(col["levels"])
    if skew["kind"] == "uniform":
        p = np.ones(k)
    elif skew["kind"] == "zipf":
        p = 1.0 / np.arange(1, k + 1) ** float(skew["s"])
    else:
        raise ValueError(f"unknown skew kind {skew['kind']!r}")
    return p / p.sum()


def _draw_codes(col, rng, n) -> np.ndarray:
    cdf = np.cumsum(level_probs(col))
    cdf[-1] = 1.0
    return np.searchsorted(cdf, rng.random(n), side="right").astype(np.int32)


def _draw_real(col, rng, n, y, columns) -> np.ndarray:
    d = col["dist"]
    kind = d["kind"]
    f32 = np.float32
    if kind == "normal":
        z = rng.standard_normal(n, dtype=f32)
        mu = f32(d.get("mu", 0.0)) + f32(d.get("class_shift", 0.0)) * y
        sg = f32(d.get("sigma", 1.0)) * np.where(
            y > 0, f32(d.get("class_scale", 1.0)), f32(1.0))
        return (mu + sg * z).astype(f32)
    if kind == "lognormal":
        z = rng.standard_normal(n, dtype=f32)
        lv = (f32(d["mu"]) + f32(d.get("class_shift", 0.0)) * y
              + f32(d["sigma"]) * z)
        mix = d.get("mix")
        if mix:
            lv = lv + f32(mix["coef"]) * np.log1p(columns[mix["column"]])
        v = np.exp(lv, dtype=f32)
        if "clip" in d:
            v = np.clip(v, f32(d["clip"][0]), f32(d["clip"][1]))
        if d.get("round"):
            v = np.round(v)
        return v.astype(f32)
    if kind == "uniform":
        return rng.uniform(d["lo"], d["hi"], n).astype(f32)
    if kind == "discrete":
        vals = np.asarray(d["values"], dtype=f32)
        p = np.asarray(d["probs"], dtype=np.float64)
        u = rng.random(n)
        tilt = float(d.get("class_tilt", 0.0))
        # positives move ``tilt`` of the first value's mass onto the others
        shift = np.full(len(p), tilt / (len(p) - 1))
        shift[0] = -tilt
        cdf0 = np.cumsum(p)
        cdf1 = np.cumsum(p + shift)
        idx0 = np.searchsorted(cdf0, u, side="right")
        idx1 = np.searchsorted(cdf1, u, side="right")
        idx = np.where(y > 0, idx1, idx0).clip(0, len(vals) - 1)
        return vals[idx]
    if kind == "clock":
        peaks = np.asarray(d["peak_minutes"], dtype=f32)
        which = rng.integers(0, len(peaks), n)
        m = peaks[which] + f32(d["sigma_minutes"]) * rng.standard_normal(
            n, dtype=f32)
        m = np.clip(np.round(m), 0, 1439).astype(np.int32)
        return (100 * (m // 60) + m % 60).astype(f32)
    raise ValueError(f"unknown dist kind {kind!r}")


def _logit(rule, cfg_columns, codes, reals) -> np.ndarray:
    n = len(next(iter(codes.values()))) if codes else len(
        next(iter(reals.values())))
    z = np.full(n, float(rule["intercept"]), dtype=np.float64)
    by_name = {c["name"]: (i, c) for i, c in enumerate(cfg_columns)}
    for term in rule["terms"]:
        i, col = by_name[term["column"]]
        if term["kind"] == "level_effects":
            eff = np.random.default_rng(
                [int(rule["rule_seed"]), i]).normal(
                    0.0, float(term["scale"]), int(col["levels"]))
            z += eff[codes[col["name"]]]
        elif term["kind"] == "linear":
            x = reals[col["name"]].astype(np.float64)
            z += float(term["coef"]) * (x - float(term["center"])) / float(
                term["scale"])
        else:
            raise ValueError(f"unknown term kind {term['kind']!r}")
    return z


def generate(config: Dict[str, Any], seed: int, rows: int) -> Generated:
    """``rows`` rows of ``config``'s schema from ``seed``."""
    cols: List[Dict[str, Any]] = config["columns"]
    rule = config["label_rule"]
    n = int(rows)
    y = np.zeros(n, dtype=np.float32)
    true_prob = None
    if rule["kind"] == "latent_class":
        y = (_rng(seed, _LABEL_STREAM).random(n)
             < float(rule["positive_rate"])).astype(np.float32)
    elif rule["kind"] != "logistic":
        raise ValueError(f"unknown label rule {rule['kind']!r}")

    out: Dict[str, np.ndarray] = {}
    types: Dict[str, str] = {}
    codes: Dict[str, np.ndarray] = {}
    for i, col in enumerate(cols):
        rng = _rng(seed, i)
        types[col["name"]] = col["type"]
        if col["type"] == "PickList":
            codes[col["name"]] = _draw_codes(col, rng, n)
            out[col["name"]] = level_names(col)[codes[col["name"]]]
        elif col["type"] == "Real":
            out[col["name"]] = _draw_real(col, rng, n, y, out)
        else:
            raise ValueError(f"unknown column type {col['type']!r}")

    if rule["kind"] == "logistic":
        reals = {k: v for k, v in out.items() if types[k] == "Real"}
        z = _logit(rule, cols, codes, reals)
        true_prob = 1.0 / (1.0 + np.exp(-z))
        y = (_rng(seed, _LABEL_STREAM).random(n) < true_prob).astype(
            np.float32)
    return Generated(out, types, y, true_prob)
