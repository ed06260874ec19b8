"""The plain reference of the regression cells: numpy only, nothing of the
program imported (``benchmark/reference.py`` is, for the one-hot and real
slots of the feature matrix, the tree descent, the bfloat16 rounding and the
selector's reserved split).

What it recomputes from raw rows and from parameters handed over as plain
numpy arrays:

* ``feature_matrix``: the vector the model reads, from raw columns and slot
  descriptions ``(parent, indicator, descriptor)``: one-hot pivots, OTHER
  and null indicators and reals as ``reference.feature_matrix`` makes them;
  the four circular periods of a timestamp (``HourOfDay_sin`` ...
  ``DayOfYear_cos``: sine and cosine of ``2 pi (value - offset) / period``
  over epoch milliseconds, joda's Monday = 1); an integer column's value
  with its null indicator;
* ``predict``: the winner's prediction: a linear or generalised-linear
  margin in float64 (through the family's mean function), a regression
  forest's mean leaf value and a boosted regressor's ``f0 + eta * sum`` by
  descent on raw float32 values against float32 thresholds, float64
  accumulation;
* ``fit_linear``: the stock linear objective (Spark ML's:
  ``mean (y - x_s'a - b)^2 / 2 + reg (alpha |a|_1 + (1 - alpha)/2 |a|^2)``
  over the features standardised on the rows fitted, population deviation,
  a constant column keeps coefficient 0, intercept free) in float64: closed
  form for ridge, accelerated proximal gradient on the Gram system until
  the gradient mapping's norm is under 1e-10 for an L1 term;
* ``fit_glm``: the generalised-linear objective as ``models/glm.py`` states
  it (``mean deviance + reg/2 |coef|^2`` on the features as they are,
  intercept free): gaussian in closed form, poisson (log link,
  ``mean (mu - y eta)``) by Newton steps (IRLS) from the family's initial
  mean until the gradient's norm is under 1e-10;
* ``rmse``, ``mse``, ``mae``, ``r2``: Spark's ``RegressionEvaluator``;
  ``cv_rmse``: the k-fold RMSE of a fit on folds of the reference's own.

Controls: ``precision="bf16"`` rounds features, thresholds and coefficients
(for a fit: the standardised or centred features and the label's deviation)
to bfloat16, the step below the float32 the configuration states.
``ista_linear`` and ``irls_from_zero`` are the schedules the program had
before PR 30 (60 ISTA steps at ``1 / trace``; IRLS from ``theta = 0`` with a
deviance clipped at 30), kept here only so that the limits can be shown to
fail them.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from . import reference
from .reference import reserved_split, to_bf16  # noqa: F401

LINEAR = "OpLinearRegression"
GLM = "OpGeneralizedLinearRegression"
RF = "OpRandomForestRegressor"
GBT = "OpGBTRegressor"
GAUSSIAN, POISSON = 0.0, 1.0
#: a grid point names its family by word, fitted parameters by code
FAMILY_CODES = {"gaussian": GAUSSIAN, "poisson": POISSON}

#: (parent feature, indicator value or None, descriptor value or None)
Slot = Tuple[str, Optional[str], Optional[str]]

_HOUR_MS = 3_600_000
_DAY_MS = 86_400_000
#: period -> (length, offset) of the circular encodings
_PERIODS = {"HourOfDay": (24.0, 0.0), "DayOfWeek": (7.0, 1.0),
            "DayOfMonth": (31.0, 1.0), "DayOfYear": (366.0, 1.0)}


# ---------------------------------------------------------------------------
# Feature vector
# ---------------------------------------------------------------------------

def period_value(ms: np.ndarray, period: str) -> np.ndarray:
    """The calendar value of epoch milliseconds (UTC): hour 0..23, day of
    the week 1..7 from Monday, day of the month and of the year from 1."""
    ms = np.asarray(ms, dtype=np.int64)
    if period == "HourOfDay":
        return (ms // _HOUR_MS) % 24
    days = ms // _DAY_MS
    if period == "DayOfWeek":
        return (days + 3) % 7 + 1                    # 1970-01-01: Thursday
    date = days.astype("datetime64[D]")
    unit = {"DayOfMonth": "datetime64[M]", "DayOfYear": "datetime64[Y]"}
    first = date.astype(unit[period]).astype("datetime64[D]")
    return (date - first).astype(np.int64) + 1


def unit_circle(ms: np.ndarray, descriptor: str) -> np.ndarray:
    period, part = descriptor.rsplit("_", 1)
    length, offset = _PERIODS[period]
    radians = 2.0 * np.pi * (period_value(ms, period) - offset) / length
    return {"sin": np.sin, "cos": np.cos}[part](radians).astype(np.float32)


def feature_matrix(raw: Dict[str, np.ndarray], types: Dict[str, str],
                   full_slots: Sequence[Slot], kept_slots: Sequence[Slot]
                   ) -> np.ndarray:
    """(n, len(kept_slots)) float32 design matrix from raw columns. The
    generated tables have no nulls: a timestamp's or an integer's null
    indicator is 0 on every row."""
    plain = ("PickList", "Real")
    two = lambda slots: [(p, i) for p, i, _ in slots if types[p] in plain]
    base = reference.feature_matrix(raw, types, two(full_slots),
                                    two(kept_slots))
    n = len(next(iter(raw.values())))
    out = np.zeros((n, len(kept_slots)), dtype=np.float32)
    j_plain = 0
    for j, (parent, ind, desc) in enumerate(kept_slots):
        if types[parent] in plain:
            out[:, j] = base[:, j_plain]
            j_plain += 1
        elif ind == reference.NULL_INDICATOR:
            continue
        elif types[parent] == "DateTime":
            out[:, j] = unit_circle(raw[parent], desc)
        elif types[parent] == "Integral":
            out[:, j] = np.asarray(raw[parent], dtype=np.float32)
        else:
            raise ValueError(f"no plain form of a {types[parent]} slot")
    return out


# ---------------------------------------------------------------------------
# Fitted families
# ---------------------------------------------------------------------------

def glm_mean(margin: np.ndarray, family: float) -> np.ndarray:
    if float(family) == GAUSSIAN:
        return margin
    return np.exp(np.clip(margin, -30.0, 30.0))


def _margin(X, params, precision):
    coef = np.asarray(params["coef"], dtype=np.float64)
    if precision == "bf16":
        X = to_bf16(np.asarray(X, dtype=np.float32))
        coef = to_bf16(coef.astype(np.float32)).astype(np.float64)
    return X.astype(np.float64) @ coef + float(params["bias"])


def _tree_sum(X, params, precision, leaf_of):
    edges = reference._edges(params)
    Xq = reference._q(X, precision)
    mask = np.asarray(params["tree_mask"], dtype=np.float32)
    acc = np.zeros(X.shape[0], dtype=np.float64)
    for t in range(mask.shape[0]):
        if mask[t] != 0:
            acc += mask[t] * leaf_of(t, Xq, edges).astype(np.float64)
    return acc, float(mask.sum())


def _forest_mean(X, params, precision):
    leaf = np.asarray(params["leaf"], dtype=np.float32)      # (T, L, 1)
    acc, live = _tree_sum(
        X, params, precision, lambda t, Xq, edges: leaf[
            t, reference._leaf_indices(Xq, params, t, edges, precision), 0])
    return acc / max(live, 1.0)


def _gbt_value(X, params, precision):
    leaf = np.asarray(params["leaf"], dtype=np.float32)      # (T, 1, L)
    acc, _ = _tree_sum(
        X, params, precision, lambda t, Xq, edges: leaf[
            t, 0, reference._leaf_indices(Xq, params, t, edges, precision,
                                          lead=(0,))])
    return (float(np.asarray(params["f0"]).reshape(-1)[0])
            + float(np.asarray(params["eta"]).reshape(-1)[0]) * acc)


_FAMILIES: Dict[str, Callable] = {
    LINEAR: _margin,
    GLM: lambda X, p, prec: glm_mean(_margin(X, p, prec),
                                     float(np.asarray(p["family"]))),
    RF: _forest_mean,
    GBT: _gbt_value,
}


def predict(family: str, params: Dict[str, Any], X: np.ndarray,
            precision: str = "f32", block: int = 65536) -> np.ndarray:
    """The winner's prediction over ``X`` (n, d) float32 in float64, in
    blocks of rows. Raises KeyError for a family it has no plain form of."""
    fn = _FAMILIES[family]
    params = {k: np.asarray(v) for k, v in params.items()}
    return np.concatenate([fn(X[lo:lo + block], params, precision)
                           for lo in range(0, X.shape[0], block)])


# ---------------------------------------------------------------------------
# Metrics (Spark's RegressionEvaluator)
# ---------------------------------------------------------------------------

def mse(pred: np.ndarray, y: np.ndarray) -> float:
    e = np.asarray(pred, dtype=np.float64) - np.asarray(y, dtype=np.float64)
    return float(np.mean(e * e))


def rmse(pred: np.ndarray, y: np.ndarray) -> float:
    return float(np.sqrt(mse(pred, y)))


def mae(pred: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(np.abs(np.asarray(pred, dtype=np.float64)
                                - np.asarray(y, dtype=np.float64))))


def r2(pred: np.ndarray, y: np.ndarray) -> float:
    y = np.asarray(y, dtype=np.float64)
    total = float(np.sum((y - y.mean()) ** 2))
    return 1.0 - mse(pred, y) * len(y) / max(total, 1e-300)


# ---------------------------------------------------------------------------
# The linear fit at its optimum
# ---------------------------------------------------------------------------

def _gram(Z: np.ndarray, block: int = 262144) -> np.ndarray:
    """``Z'Z`` in float64, in blocks of rows."""
    G = np.zeros((Z.shape[1], Z.shape[1]))
    for lo in range(0, Z.shape[0], block):
        Zb = np.asarray(Z[lo:lo + block], dtype=np.float64)
        G += Zb.T @ Zb
    return G


def _moments(X: np.ndarray, y: np.ndarray, precision: str,
             standardise: bool):
    """(mean, std, live, C, c, y_bar): the columns' means and population
    deviations, and the covariance (correlation where ``standardise``) of
    the features and their cross moment with the label's deviation, in
    float64. ``bf16``: the centred (standardised) features and the label's
    deviation rounded to bfloat16 before the sums."""
    n = X.shape[0]
    Xc = np.array(X, dtype=np.float64)
    mean = Xc.mean(axis=0)
    Xc -= mean
    std = np.sqrt(np.einsum("ij,ij->j", Xc, Xc) / n)
    live = std > 0
    if standardise:
        Xc /= np.where(live, std, 1.0)
    y = np.asarray(y, dtype=np.float64)
    y_bar = float(y.mean())
    yc = y - y_bar
    if precision == "bf16":
        Xc = to_bf16(Xc.astype(np.float32))
        yc = to_bf16(yc.astype(np.float32)).astype(np.float64)
    elif precision != "f64":
        raise ValueError(f"unknown precision {precision!r}")
    G = _gram(np.concatenate([Xc, yc[:, None]], axis=1)) / n
    return mean, std, live, G[:-1, :-1], G[:-1, -1], y_bar


def _prox_gradient(C, c, l1, l2, a, tol, max_iter):
    """min ``a'Ca/2 - c'a + l2/2 |a|^2 + l1 |a|_1``: accelerated proximal
    gradient at the step ``1 / (lambda_max(C) + l2)``, restarted where the
    momentum points uphill, until the gradient mapping's largest entry is
    under ``tol``."""
    lips = float(np.linalg.eigvalsh(C)[-1]) + l2
    u, t, it = a.copy(), 1.0, 0
    for it in range(1, max_iter + 1):
        w = u - (C @ u + l2 * u - c) / lips
        a_new = np.sign(w) * np.maximum(np.abs(w) - l1 / lips, 0.0)
        if np.abs(u - a_new).max() * lips < tol:
            a = a_new
            break
        if (u - a_new) @ (a_new - a) > 0:
            t_new, u = 1.0, a_new
        else:
            t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
            u = a_new + (t - 1.0) / t_new * (a_new - a)
        a, t = a_new, t_new
    return a, it


def _unstandardise(a, mean, std, live, y_bar) -> Dict[str, Any]:
    coef = np.where(live, a / np.where(live, std, 1.0), 0.0)
    return {"coef": coef, "bias": float(y_bar - coef @ mean), "std": std}


def fit_linear(X: np.ndarray, y: np.ndarray, reg_param: float,
               elastic_net: float, precision: str = "f64",
               tol: float = 1e-10, max_iter: int = 2_000_000
               ) -> Dict[str, Any]:
    """The stock linear regression at its optimum (module docstring).
    Returns ``coef`` and ``bias`` in the features' own scale, the deviations
    ``std`` and the ``iterations`` of the proximal gradient (0: closed
    form)."""
    mean, std, live, C, c, y_bar = _moments(X, y, precision, True)
    l1 = float(reg_param) * float(elastic_net)
    l2 = float(reg_param) * (1.0 - float(elastic_net))
    d = len(mean)
    dead = ~live
    K = C + l2 * np.eye(d)
    K[dead, dead] = 1.0
    a = np.where(live, np.linalg.lstsq(K, np.where(live, c, 0.0),
                                       rcond=None)[0], 0.0)
    it = 0
    if l1 > 0:
        a, it = _prox_gradient(C, np.where(live, c, 0.0), l1, l2, a, tol,
                               max_iter)
    return dict(_unstandardise(a, mean, std, live, y_bar), iterations=it)


def ista_linear(X: np.ndarray, y: np.ndarray, reg_param: float,
                elastic_net: float, steps: int = 60) -> Dict[str, Any]:
    """A control: the program's schedule before PR 30. The ridge solve of
    the augmented system, then ``steps`` plain proximal steps at
    ``1 / trace`` of it."""
    mean, std, live, C, c, y_bar = _moments(X, y, "f64", True)
    l1 = float(reg_param) * float(elastic_net)
    l2 = float(reg_param) * (1.0 - float(elastic_net))
    d = len(mean)
    A = np.zeros((d + 1, d + 1))
    A[:d, :d] = C + l2 * np.eye(d)
    A[d, d] = 1.0
    A += 1e-8 * np.eye(d + 1)
    rhs = np.r_[np.where(live, c, 0.0), y_bar]
    theta = np.linalg.solve(A, rhs)
    if l1 > 0:
        step = 1.0 / max(float(np.trace(A)), 1e-6)
        for _ in range(int(steps)):
            t = theta - step * (A @ theta - rhs)
            theta = np.r_[np.sign(t[:d]) * np.maximum(
                np.abs(t[:d]) - step * l1, 0.0), t[d]]
    return _unstandardise(theta[:d], mean, std, live, float(theta[d]))


# ---------------------------------------------------------------------------
# The generalised-linear fit at its optimum
# ---------------------------------------------------------------------------

def fit_glm(X: np.ndarray, y: np.ndarray, reg_param: float, family: float,
            precision: str = "f64", tol: float = 1e-10, max_iter: int = 100
            ) -> Dict[str, Any]:
    """The generalised-linear regression at its optimum (module docstring):
    ``coef``, ``bias``, ``family``, the deviations ``std`` (for comparing
    coefficients in standardised units) and the Newton ``iterations``."""
    reg = float(reg_param)
    if float(family) == GAUSSIAN:
        mean, std, _, C, c, y_bar = _moments(X, y, precision, False)
        coef = np.linalg.solve(C + (reg + 1e-12) * np.eye(len(mean)), c)
        return {"coef": coef, "bias": float(y_bar - coef @ mean),
                "family": GAUSSIAN, "std": std, "iterations": 0}
    if float(family) != POISSON:
        raise ValueError(f"no plain form of family code {family!r}")
    n, d = X.shape
    y = np.asarray(y, dtype=np.float64)
    Xc = np.array(X, dtype=np.float64)
    mean = Xc.mean(axis=0)
    Xc -= mean
    std = np.sqrt(np.einsum("ij,ij->j", Xc, Xc) / n)
    if precision == "bf16":
        Xc = to_bf16(Xc.astype(np.float32)).astype(np.float64)
    mu = np.maximum(y, 0.1)
    eta = np.log(mu)
    beta, b, it = np.zeros(d), 0.0, 0
    pen = np.r_[np.full(d, reg), 0.0]
    Xa = np.concatenate([Xc, np.ones((n, 1))], axis=1)
    for it in range(1, max_iter + 1):
        z = eta + (y - mu) / mu
        A = (Xa * mu[:, None]).T @ Xa / n + np.diag(pen + 1e-12)
        theta = np.linalg.solve(A, Xa.T @ (mu * z) / n)
        beta, b = theta[:d], float(theta[d])
        eta = np.clip(Xc @ beta + b, -30.0, 30.0)
        mu = np.maximum(np.exp(eta), 1e-12)
        grad = np.r_[Xc.T @ (mu - y) / n + reg * beta, np.mean(mu - y)]
        if np.abs(grad).max() < tol:
            break
    return {"coef": beta, "bias": float(b - beta @ mean), "family": POISSON,
            "std": std, "iterations": it}


def irls_from_zero(X: np.ndarray, y: np.ndarray, reg_param: float,
                   family: float, iters: int = 25) -> Dict[str, Any]:
    """A control: the program's IRLS before PR 30, from ``theta = 0``
    (``mu = 1``), the deviance clipped at 30 and the best iterate kept. For
    a label whose mean is far from 1 the first step leaves the clip's range
    and the zero vector stays the best: the prediction is ``exp(0) = 1``."""
    n, d = X.shape
    y = np.asarray(y, dtype=np.float64)
    Xa = np.concatenate([np.asarray(X, dtype=np.float64),
                         np.ones((n, 1))], axis=1)
    gauss = float(family) == GAUSSIAN
    pen = np.diag(np.r_[np.full(d, float(reg_param)), 0.0]) \
        + 1e-8 * np.eye(d + 1)

    def deviance(theta):
        eta = np.clip(Xa @ theta, -30.0, 30.0)
        return float(np.mean(0.5 * (y - eta) ** 2 if gauss
                             else np.exp(eta) - y * eta))

    theta = best = np.zeros(d + 1)
    best_loss = deviance(theta)
    for _ in range(int(iters)):
        eta = Xa @ theta
        mu = np.maximum(np.exp(np.clip(eta, -30.0, 30.0)), 1e-12)
        w = np.ones(n) if gauss else mu
        z = y if gauss else np.clip(eta + (y - mu) / mu, -1e6, 1e6)
        prop = np.linalg.solve((Xa * w[:, None]).T @ Xa / n + pen,
                               Xa.T @ (w * z) / n)
        theta = prop if np.isfinite(prop).all() else theta
        loss = deviance(theta)
        if loss < best_loss:
            best, best_loss = theta, loss
    return {"coef": best[:d], "bias": float(best[d]), "family": float(family),
            "std": np.asarray(X, dtype=np.float64).std(axis=0)}


# ---------------------------------------------------------------------------
# Cross-validation of a point
# ---------------------------------------------------------------------------

def fit_point(family: str, hyper: Dict[str, Any], X: np.ndarray,
              y: np.ndarray, precision: str = "f64") -> Dict[str, Any]:
    """The reference's fit of one grid point of a linear family."""
    if family == LINEAR:
        return fit_linear(X, y, hyper["regParam"],
                          hyper.get("elasticNetParam", 0.0), precision)
    if family == GLM:
        code = hyper.get("family", GAUSSIAN)
        return fit_glm(X, y, hyper["regParam"],
                       FAMILY_CODES.get(code, code), precision)
    raise KeyError(family)


def cv_rmse(X: np.ndarray, y: np.ndarray, family: str,
            hyper: Dict[str, Any], folds: int, seed: int,
            precision: str = "f64") -> float:
    """Mean over ``folds`` folds of the RMSE, on a fold's rows, of the
    point's fit on the others. The folds are the reference's own (every
    ``folds``-th row of a permutation drawn from ``seed``) and every row of
    a fold is scored."""
    n = X.shape[0]
    perm = np.random.default_rng([int(seed), 7]).permutation(n)
    out = []
    for f in range(int(folds)):
        val = np.zeros(n, dtype=bool)
        val[perm[f::int(folds)]] = True
        fit = fit_point(family, hyper, X[~val], y[~val], precision)
        out.append(rmse(predict(family, fit, X[val], "f32"
                                if precision == "f64" else precision),
                        y[val]))
    return float(np.mean(out))
