"""The plain reference: numpy only, nothing of the program imported.

It recomputes, from raw rows, what the program's scoring path produces:

* the feature vector (one-hot pivots, OTHER and null indicators, real
  values) from the column descriptions of the program's vector metadata,
  handed over as plain tuples;
* the winner's class-1 score from its fitted parameters, handed over as
  plain numpy arrays: linear margin / logistic probability, random-forest
  mean leaf probability, gradient-boosted margin through a sigmoid. Trees
  are descended row by row on raw float32 values against float32
  thresholds (``edges[feature, bin]``), never on the program's bin codes.

It also retrains what the timed path trains, where a plain form exists:
``fit_logistic`` is the L2-regularised logistic regression of the stock
grid (Spark ML's conventions: mean log-loss + regParam/2 * |w|^2 over
features standardised on the rows fitted, intercept free) solved to its
optimum by Newton steps in float64, ``reserved_split`` the rows the stock
selector fits on, ``cv_aupr`` the k-fold AuPR of such a fit on folds of
the reference's own.

``precision="bf16"`` is the control: the same arithmetic with features,
thresholds and coefficients (for a fit: the standardised features and every
per-row temporary) rounded to bfloat16 first, the step below the float32
the configurations state. ``auroc`` is a rank-sum AuROC, independent of
the program's metric kernels (copied from chip_smoke.py); ``aupr`` the
trapezoid area under the precision-recall curve over distinct scores.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

NULL_INDICATOR = "NullIndicatorValue"
OTHER_INDICATOR = "OTHER"

#: (parent feature name, indicator value or None) per vector slot
Slot = Tuple[str, Optional[str]]


def auroc(scores: np.ndarray, y: np.ndarray) -> float:
    """Rank-sum AuROC with tie-averaged ranks."""
    _, inv, counts = np.unique(scores, return_inverse=True,
                               return_counts=True)
    upper = np.cumsum(counts)
    ranks = (upper - (counts - 1) / 2.0)[inv]
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def aupr(scores: np.ndarray, y: np.ndarray) -> float:
    """Area under the precision-recall curve: one point per distinct score,
    joined by straight lines, starting at recall 0, precision 1 (Spark's
    areaUnderPR up to that first point)."""
    order = np.argsort(-np.asarray(scores, dtype=np.float64), kind="stable")
    s = np.asarray(scores)[order]
    pos = np.asarray(y)[order] > 0.5
    last = np.r_[s[1:] != s[:-1], True]
    tp = np.cumsum(pos)[last].astype(np.float64)
    fp = np.cumsum(~pos)[last].astype(np.float64)
    rec = tp / max(tp[-1], 1.0)
    prec = tp / np.maximum(tp + fp, 1.0)
    return float(((rec - np.r_[0.0, rec[:-1]])
                  * (prec + np.r_[1.0, prec[:-1]]) / 2.0).sum())


def to_bf16(a: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even), kept
    in float32 storage."""
    u = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def _q(a: np.ndarray, precision: str) -> np.ndarray:
    a = np.asarray(a, dtype=np.float32)
    if precision == "f32":
        return a
    if precision == "bf16":
        return to_bf16(a)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# Feature vector
# ---------------------------------------------------------------------------

def feature_matrix(raw: Dict[str, np.ndarray], types: Dict[str, str],
                   full_slots: Sequence[Slot], kept_slots: Sequence[Slot],
                   real_fills: Optional[Dict[str, float]] = None
                   ) -> np.ndarray:
    """(n, len(kept_slots)) float32 design matrix from raw columns.

    ``full_slots`` describe the combined vector before the sanity checker
    (needed for OTHER: a level is OTHER when it is none of the pivoted
    levels, whether or not the checker kept their columns); ``kept_slots``
    the vector the model reads. A ``PickList`` value of None is null; a
    ``Real`` has no nulls here unless ``real_fills`` names its fill."""
    n = len(next(iter(raw.values())))
    pivots: Dict[str, List[str]] = {}
    for parent, ind in full_slots:
        if types[parent] == "PickList" and ind not in (
                None, NULL_INDICATOR, OTHER_INDICATOR):
            pivots.setdefault(parent, []).append(ind)
    out = np.zeros((n, len(kept_slots)), dtype=np.float32)
    null_of: Dict[str, np.ndarray] = {}
    in_pivot: Dict[str, np.ndarray] = {}
    for j, (parent, ind) in enumerate(kept_slots):
        col = raw[parent]
        if types[parent] == "PickList":
            if parent not in null_of:
                null_of[parent] = np.equal(col, None)
            if ind == NULL_INDICATOR:
                out[:, j] = null_of[parent]
            elif ind == OTHER_INDICATOR:
                if parent not in in_pivot:
                    in_pivot[parent] = np.isin(
                        col, np.array(pivots.get(parent, []), dtype=object))
                out[:, j] = ~in_pivot[parent] & ~null_of[parent]
            else:
                out[:, j] = (col == ind)
        elif ind == NULL_INDICATOR:
            out[:, j] = np.isnan(col)
        else:
            v = np.asarray(col, dtype=np.float32)
            if real_fills and parent in real_fills:
                v = np.where(np.isnan(v), np.float32(real_fills[parent]), v)
            out[:, j] = v
    return out


# ---------------------------------------------------------------------------
# Fitted families
# ---------------------------------------------------------------------------

def _sigmoid(z: np.ndarray) -> np.ndarray:
    z = np.asarray(z, dtype=np.float32)
    return (1.0 / (1.0 + np.exp(-z, dtype=np.float32))).astype(np.float32)


def _linear_margin(X, params, precision):
    Xq, cq = _q(X, precision), _q(params["coef"], precision)
    # float32 products, float64 accumulation: the plain answer
    return (Xq.astype(np.float64) @ cq.astype(np.float64)
            + float(params["bias"])).astype(np.float32)


def _thresholds(feat, bins, edges, precision):
    """float32 split thresholds ``edges[feature, bin]``; a bin at or past
    the edge table is a stopped node: +inf, every row goes left."""
    n_edges = edges.shape[-1]
    stopped = bins >= n_edges
    thr = _q(edges, precision)[feat, np.minimum(bins, n_edges - 1)]
    return np.where(stopped, np.float32(np.inf), thr)


def _descend_chain(Xq, feat_lv, bins_lv, base_lv, edges, precision):
    """(n,) final slot of each row in one slot-chain tree."""
    n = Xq.shape[0]
    rows = np.arange(n)
    slot = np.zeros(n, dtype=np.int64)
    thr_lv = _thresholds(feat_lv, bins_lv, edges, precision)
    for level in range(feat_lv.shape[0]):
        f = feat_lv[level, slot]
        go = Xq[rows, f] > thr_lv[level, slot]
        slot = base_lv[level, slot] + go
    return slot


def _descend_heap(Xq, feat, bins, edges, depth, precision):
    """(n,) leaf index of each row in one complete-heap tree."""
    n = Xq.shape[0]
    rows = np.arange(n)
    node = np.zeros(n, dtype=np.int64)
    thr = _thresholds(feat, bins, edges, precision)
    for level in range(depth):
        idx = (2 ** level - 1) + node
        go = Xq[rows, feat[idx]] > thr[idx]
        node = 2 * node + go
    return node


def _edges(params) -> np.ndarray:
    e = np.asarray(params["edges"], dtype=np.float32)
    return e[0] if e.ndim == 3 else e


def _leaf_indices(Xq, params, t, edges, precision, lead=()):
    """Leaf slot of every row in tree ``t`` (``lead`` indexes extra axes
    between the tree axis and the tables, e.g. the GBT class axis)."""
    ix = (t,) + tuple(lead)
    if "base_lv" in params:
        return _descend_chain(
            Xq, np.asarray(params["feat_lv"])[ix],
            np.asarray(params["bins_lv"])[ix],
            np.asarray(params["base_lv"])[ix], edges, precision)
    feat = np.asarray(params["feat"])[ix]
    depth = int(round(np.log2(feat.shape[-1] + 1)))
    return _descend_heap(Xq, feat, np.asarray(params["bins"])[ix], edges,
                         depth, precision)


def _forest_prob(X, params, precision):
    edges = _edges(params)
    Xq = _q(X, precision)
    leaf = np.asarray(params["leaf"], dtype=np.float32)     # (T, L, k)
    mask = np.asarray(params["tree_mask"], dtype=np.float32)
    acc = np.zeros(X.shape[0], dtype=np.float64)
    for t in range(leaf.shape[0]):
        if mask[t] == 0:
            continue
        slot = _leaf_indices(Xq, params, t, edges, precision)
        acc += mask[t] * leaf[t, slot, 1].astype(np.float64)
    return (acc / max(float(mask.sum()), 1.0)).astype(np.float32)


def _gbt_prob(X, params, precision):
    edges = _edges(params)
    Xq = _q(X, precision)
    leaf = np.asarray(params["leaf"], dtype=np.float32)     # (T, C, L)
    mask = np.asarray(params["tree_mask"], dtype=np.float32)
    acc = np.zeros(X.shape[0], dtype=np.float64)
    for t in range(leaf.shape[0]):
        if mask[t] == 0:
            continue
        slot = _leaf_indices(Xq, params, t, edges, precision, lead=(0,))
        acc += mask[t] * leaf[t, 0, slot].astype(np.float64)
    f0 = float(np.asarray(params["f0"]).reshape(-1)[0])
    eta = float(np.asarray(params["eta"]).reshape(-1)[0])
    return _sigmoid((f0 + eta * acc).astype(np.float32))


#: family name -> (the score the program reports for class 1, function)
_FAMILIES = {
    "OpLogisticRegression": (
        "probability_1",
        lambda X, p, prec: _sigmoid(_linear_margin(X, p, prec))),
    "OpLinearSVC": ("rawPrediction_1", _linear_margin),
    "OpRandomForestClassifier": ("probability_1", _forest_prob),
    "OpGBTClassifier": ("probability_1", _gbt_prob),
}


def score_key(family: str) -> str:
    """Which key of the program's Prediction column ``class1_score`` of
    this family is compared with."""
    return _FAMILIES[family][0]


def class1_score(family: str, params: Dict[str, Any], X: np.ndarray,
                 precision: str = "f32", block: int = 65536) -> np.ndarray:
    """The winner's class-1 score over ``X`` (n, d) float32, in blocks of
    rows. Raises KeyError for a family it has no plain form of."""
    fn = _FAMILIES[family][1]
    params = {k: np.asarray(v) for k, v in params.items()}
    return np.concatenate([fn(X[lo:lo + block], params, precision)
                           for lo in range(0, X.shape[0], block)])


# ---------------------------------------------------------------------------
# Training: the stock selector's rows, and the logistic fit at its optimum
# ---------------------------------------------------------------------------

def reserved_split(n: int, fraction: float, seed: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """(fitted rows, reserved rows), each sorted: the stock selector's
    stated rule. It sets ``round(n * fraction)`` rows aside for its own
    hold-out evaluation, the first of ``RandomState(seed).permutation(n)``,
    and validates and refits on the rest."""
    perm = np.random.RandomState(int(seed)).permutation(int(n))
    n_test = int(round(int(n) * float(fraction)))
    return np.sort(perm[n_test:]), np.sort(perm[:n_test])


def _round_like(precision: str):
    if precision == "f64":
        return lambda v: v
    if precision == "bf16":
        return lambda v: to_bf16(np.asarray(v, dtype=np.float32)).astype(
            np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def _curvature(Xs: np.ndarray, s: np.ndarray, block: int = 65536
               ) -> Tuple[np.ndarray, np.ndarray]:
    """``Xs' diag(s) Xs / n`` and ``Xs' s / n`` in blocks of rows. The
    products are float32 and the sums over blocks float64: the curvature
    sets how fast the Newton steps close in, not where they end."""
    n, d = Xs.shape
    H = np.zeros((d, d))
    for lo in range(0, n, block):
        Xb = Xs[lo:lo + block].astype(np.float32)
        H += Xb.T @ (Xb * s[lo:lo + block, None].astype(np.float32))
    return H / n, Xs.T @ s / n


def fit_logistic(X: np.ndarray, y: np.ndarray, reg_param: float,
                 precision: str = "f64",
                 start: Optional[Tuple[np.ndarray, float]] = None,
                 max_iter: int = 30, tol: float = 1e-10) -> Dict[str, Any]:
    """Binary logistic regression at its optimum: minimise
    ``mean(log-loss) + reg_param / 2 * |a|^2`` over the coefficients ``a``
    of the features standardised on these rows (population deviation; a
    constant column keeps coefficient 0) and a free intercept, by damped
    Newton steps in float64. Returns ``coef`` and ``bias`` in the features'
    own scale, the deviations ``std`` and the ``iterations`` taken.

    ``start`` is a (coef, bias) to start from. ``precision="bf16"`` is the
    control: the standardised features and every per-row temporary (margin,
    probability, residual, curvature) are rounded to bfloat16 before each
    reduction, for ``max_iter`` steps."""
    q = _round_like(precision)
    n, d = X.shape
    y = np.asarray(y, dtype=np.float64)
    Xs = np.array(X, dtype=np.float64)
    mean = Xs.mean(axis=0)
    Xs -= mean
    std = np.sqrt(np.einsum("ij,ij->j", Xs, Xs) / n)
    live = std > 0
    Xs /= np.where(live, std, 1.0)
    if precision != "f64":
        Xs = q(Xs)
    if start is None:
        p0 = min(max(float(y.mean()), 1e-6), 1 - 1e-6)
        a, b = np.zeros(d), float(np.log(p0 / (1 - p0)))
    else:
        coef0 = np.asarray(start[0], dtype=np.float64)
        a = np.where(live, coef0 * std, 0.0)
        b = float(start[1]) + float(coef0 @ mean)
    l2 = float(reg_param)

    def parts(a, b):
        z = q(Xs @ q(a) + b)
        p = q(1.0 / (1.0 + np.exp(-z)))
        loss = float(np.mean(np.logaddexp(0.0, z) - y * z)
                     + 0.5 * l2 * (a @ a))
        return p, loss

    p, loss = parts(a, b)
    it = 0
    for it in range(1, max_iter + 1):
        r = q(p - y)
        s = q(np.maximum(p * (1.0 - p), 1e-12))
        K = np.zeros((d + 1, d + 1))
        K[:d, :d], K[:d, d] = _curvature(Xs, s)
        K[:d, :d] += l2 * np.eye(d)
        K[d, :d] = K[:d, d]
        K[d, d] = s.mean()
        dead = np.r_[~live, False]
        K[dead, :] = 0.0
        K[:, dead] = 0.0
        K[dead, dead] = 1.0
        g = np.r_[np.where(live, Xs.T @ r / n + l2 * a, 0.0), r.mean()]
        step = np.linalg.solve(K, g)
        t = 1.0
        while True:
            a1, b1 = a - t * step[:d], b - t * step[d]
            p1, loss1 = parts(a1, b1)
            if loss1 <= loss + 1e-12 or t < 1e-3 or precision != "f64":
                break
            t *= 0.5
        a, b, p, loss = a1, b1, p1, loss1
        if precision == "f64" and float(np.abs(step).max()) * t < tol:
            break
    coef = np.where(live, a / np.where(live, std, 1.0), 0.0)
    return {"coef": coef, "bias": float(b - coef @ mean), "std": std,
            "iterations": it}


def logistic_prob(X: np.ndarray, fit: Dict[str, Any]) -> np.ndarray:
    z = np.asarray(X, dtype=np.float64) @ fit["coef"] + fit["bias"]
    return 1.0 / (1.0 + np.exp(-z))


def cv_aupr(X: np.ndarray, y: np.ndarray, reg_param: float, folds: int,
            seed: int, start: Optional[Tuple[np.ndarray, float]] = None,
            precision: str = "f64") -> float:
    """Mean over ``folds`` folds of the AuPR, on a fold's rows, of the
    logistic fit on the others. The folds are the reference's own (every
    ``folds``-th row of a permutation drawn from ``seed``) and every row of
    a fold is scored."""
    n = X.shape[0]
    perm = np.random.default_rng([int(seed), 7]).permutation(n)
    out = []
    for f in range(int(folds)):
        val = np.zeros(n, dtype=bool)
        val[perm[f::int(folds)]] = True
        fit = fit_logistic(X[~val], y[~val], reg_param, precision, start,
                           max_iter=4 if precision != "f64" else 30,
                           tol=1e-7)
        out.append(aupr(logistic_prob(X[val], fit), y[val]))
    return float(np.mean(out))
