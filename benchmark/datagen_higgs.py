"""Rows from ``--seed`` for a configuration whose label lies in resonance
windows of its columns (``label_rule.kind`` ``mass_windows``): the columns
are drawn first, by ``datagen.py``'s distributions, and the label is
Bernoulli of a logistic rule over NON-MONOTONE functions of them. Reuses
``datagen.py``'s seed streams, column draws and ``Generated``.

The seed draws the rows only; every centre, width, cut and coefficient is
the file's. The rule:

    logit = intercept
          + sum over terms of coef * prod(window(column) for its windows)
                                   * [indicator column > above]
          + the logit of the cascade's step at which the row leaves it
          + sum over tilts of coef * (column - center) / scale

``window(x) = exp(-((log x - log center) / width)^2 / 2)``: a smooth bump
around the column's signal peak, symmetric in the logarithm, so a window
at a column's own median has no linear trend for a linear model to read. A
``term`` with two windows is their product: the signal sits where both
masses are on their peaks at once. ``tilts`` are the small linear trends the
low-level kinematics carry.

``cascade`` adds what no sum of such terms states: a selection as an
analysis makes it, cut after cut. A row goes through the ``steps`` in order;
at each step it is IN where the product of the step's windows (and its
indicator) is above ``threshold``, else OUT; the side named by ``exit``
leaves the cascade there with the step's ``logit``, the other side goes on;
a row that passes every step gets ``passed_logit``. The exits' logits
alternate in sign, so what a row's mass means depends on every cut before
it: a product of windows is a SUM in the logarithm, which shallow additive
trees rank as well as deep ones; a cascade of vetoes is not, and a tree has
to be as deep as the cascade is long.

Everything is bulk numpy: no per-row python.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from .datagen import _LABEL_STREAM, Generated, _draw_real, _rng


def window(x: np.ndarray, spec: Dict[str, Any]) -> np.ndarray:
    z = (np.log(np.maximum(x.astype(np.float64), 1e-30))
         - np.log(float(spec["center"]))) / float(spec["width"])
    return np.exp(-0.5 * z * z)


def _product(step: Dict[str, Any], columns: Dict[str, np.ndarray],
             bumps: Dict[str, np.ndarray], n: int) -> np.ndarray:
    """The product of a term's or a step's windows and its indicator."""
    s = np.ones(n)
    for name in step.get("windows", []):
        s = s * bumps[name]
    ind = step.get("indicator")
    if ind:
        s = s * (columns[ind["column"]] > float(ind["above"]))
    return s


def _cascade(spec: Dict[str, Any], columns: Dict[str, np.ndarray],
             bumps: Dict[str, np.ndarray], n: int) -> np.ndarray:
    out = np.full(n, float(spec["passed_logit"]))
    inside = np.ones(n, dtype=bool)          # still in the cascade
    for step in spec["steps"]:
        is_in = _product(step, columns, bumps, n) > float(
            step.get("threshold", 0.5))
        leaves = inside & (is_in if step["exit"] == "in" else ~is_in)
        out[leaves] = float(step["logit"])
        inside &= ~leaves
    return out


def logit(rule: Dict[str, Any], columns: Dict[str, np.ndarray]) -> np.ndarray:
    n = len(next(iter(columns.values())))
    z = np.full(n, float(rule["intercept"]), dtype=np.float64)
    bumps = {name: window(columns[name], spec)
             for name, spec in rule["windows"].items()}
    for term in rule.get("terms", []):
        z += float(term["coef"]) * _product(term, columns, bumps, n)
    if "cascade" in rule:
        z += _cascade(rule["cascade"], columns, bumps, n)
    for tilt in rule.get("tilts", []):
        x = columns[tilt["column"]].astype(np.float64)
        z += float(tilt["coef"]) * (x - float(tilt["center"])) / float(
            tilt["scale"])
    return z


def generate(config: Dict[str, Any], seed: int, rows: int) -> Generated:
    """``rows`` rows of ``config``'s schema from ``seed``."""
    rule = config["label_rule"]
    if rule["kind"] != "mass_windows":
        raise ValueError(f"unknown label rule {rule['kind']!r}")
    n = int(rows)
    no_class = np.zeros(n, dtype=np.float32)
    out: Dict[str, np.ndarray] = {}
    types: Dict[str, str] = {}
    for i, col in enumerate(config["columns"]):
        if col["type"] != "Real":
            raise ValueError(f"unknown column type {col['type']!r}")
        types[col["name"]] = "Real"
        out[col["name"]] = _draw_real(col, _rng(seed, i), n, no_class, out)
    true_prob = 1.0 / (1.0 + np.exp(-logit(rule, out)))
    y = (_rng(seed, _LABEL_STREAM).random(n) < true_prob).astype(np.float32)
    return Generated(out, types, y, true_prob)
