"""The plain reference of the multiclass cells: numpy only, nothing of the
program imported (``benchmark/reference.py`` is, for the feature matrix, the
tree descent and the selector's reserved split).

What it recomputes from raw rows and from parameters handed over as plain
numpy arrays:

* ``softmax_prob``: the C class probabilities of a fitted ``W`` (d, C) and
  ``b`` (C,) in float64;
* ``forest_prob``: a C-class random forest's mean leaf distribution, trees
  descended on raw float32 values against float32 thresholds, accumulated
  in float64;
* ``fit_softmax``: the multinomial logistic regression of the stock grid
  (Spark ML's conventions: mean cross-entropy + regParam/2 * |A|^2 over the
  coefficients of the features standardised on the rows fitted, intercepts
  free) solved in float64 by Newton steps with a conjugate-gradient inner
  solve until the largest gradient entry is under ``tol`` (1e-10);
* ``fit_elastic_net``: the same objective with an L1 term, by plain
  proximal-gradient steps (FISTA with a fixed step from the curvature's
  bound): slow and simple, for tests at small size;
* ``weighted_f1``: Spark's ``weightedFMeasure`` (per-class F1 weighted by
  the class's share of the labels);
* ``cv_f1``: the k-fold weighted F1 of such a fit on folds of its own.

Controls: ``precision="bf16"`` rounds features, thresholds, coefficients
and (for a fit) every per-row temporary to bfloat16, the step below the
float32 the configuration states; ``adam_softmax`` is the schedule the
program had before PR 26 (200 full-batch Adam steps at rate 0.1), kept here
only so that the limits can be shown to fail it.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from . import reference
from .reference import feature_matrix, reserved_split, to_bf16  # noqa: F401

RF = "OpRandomForestClassifier"
LR = "OpLogisticRegression"


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------

def centred(W: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Coefficients and intercepts with their mean over the classes taken
    out: softmax does not see that mean, so two fits are compared without
    it."""
    W = np.asarray(W, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return W - W.mean(axis=1, keepdims=True), b - b.mean()


def _softmax(Z: np.ndarray) -> np.ndarray:
    Z = Z - Z.max(axis=1, keepdims=True)
    np.exp(Z, out=Z)
    Z /= Z.sum(axis=1, keepdims=True)
    return Z


def softmax_prob(X: np.ndarray, W: np.ndarray, b: np.ndarray,
                 precision: str = "f64", block: int = 131072) -> np.ndarray:
    """(n, C) float64 probabilities of ``X`` (n, d) under ``W``, ``b``.
    ``bf16``: features and coefficients rounded to bfloat16 first."""
    W = np.asarray(W, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if precision == "bf16":
        W = to_bf16(W.astype(np.float32)).astype(np.float64)
    elif precision != "f64":
        raise ValueError(f"unknown precision {precision!r}")
    out = np.empty((X.shape[0], W.shape[1]))
    for lo in range(0, X.shape[0], block):
        Xb = X[lo:lo + block]
        if precision == "bf16":
            Xb = to_bf16(np.asarray(Xb, dtype=np.float32))
        out[lo:lo + block] = _softmax(Xb.astype(np.float64) @ W + b)
    return out


def forest_prob(X: np.ndarray, params: Dict[str, Any], num_classes: int,
                precision: str = "f32", block: int = 65536) -> np.ndarray:
    """(n, C) mean leaf distribution over the forest's live trees."""
    params = {k: np.asarray(v) for k, v in params.items()}
    edges = reference._edges(params)
    leaf = np.asarray(params["leaf"], dtype=np.float32)      # (T, L, k)
    mask = np.asarray(params["tree_mask"], dtype=np.float32)
    out = np.zeros((X.shape[0], num_classes))
    for lo in range(0, X.shape[0], block):
        Xq = reference._q(X[lo:lo + block], precision)
        acc = out[lo:lo + block]
        for t in range(leaf.shape[0]):
            if mask[t] == 0:
                continue
            slot = reference._leaf_indices(Xq, params, t, edges, precision)
            acc += mask[t] * leaf[t, slot, :num_classes].astype(np.float64)
    return out / max(float(mask.sum()), 1.0)


def class_probs(family: str, params: Dict[str, Any], X: np.ndarray,
                num_classes: int, precision: str = "f32") -> np.ndarray:
    """The winner's (n, C) probabilities. Raises KeyError for a family it
    has no plain form of."""
    if family == LR:
        return softmax_prob(X, params["W"], params["b"],
                            "f64" if precision == "f32" else precision)
    if family == RF:
        return forest_prob(X, params, num_classes, precision)
    raise KeyError(family)


def weighted_f1(pred: np.ndarray, y: np.ndarray, num_classes: int) -> float:
    """Spark's ``MulticlassMetrics.weightedFMeasure``: F1 of each class
    (0 where it has neither a prediction nor a label) weighted by the
    class's share of the labels."""
    pred = np.asarray(pred).astype(np.int64)
    y = np.asarray(y).astype(np.int64)
    C = int(num_classes)
    cm = np.bincount(y * C + pred, minlength=C * C).reshape(C, C).astype(
        np.float64)
    tp = np.diag(cm)
    support, predicted = cm.sum(axis=1), cm.sum(axis=0)
    prec = tp / np.maximum(predicted, 1.0)
    rec = tp / np.maximum(support, 1.0)
    f1 = np.where(prec + rec > 0, 2 * prec * rec
                  / np.maximum(prec + rec, 1e-300), 0.0)
    return float((f1 * support).sum() / max(cm.sum(), 1.0))


# ---------------------------------------------------------------------------
# The softmax fit at its optimum
# ---------------------------------------------------------------------------

class _Std:
    """Features standardised on the rows fitted (population deviation; a
    constant column keeps coefficient 0)."""

    def __init__(self, X: np.ndarray, precision: str):
        Xs = np.array(X, dtype=np.float64)
        self.mean = Xs.mean(axis=0)
        Xs -= self.mean
        self.std = np.sqrt(np.einsum("ij,ij->j", Xs, Xs) / Xs.shape[0])
        self.live = self.std > 0
        Xs /= np.where(self.live, self.std, 1.0)
        self.Xs = reference._round_like(precision)(Xs)

    def to_std(self, W, b):
        W = np.asarray(W, dtype=np.float64)
        A = np.where(self.live[:, None], W * self.std[:, None], 0.0)
        return A, np.asarray(b, dtype=np.float64) + self.mean @ W

    def from_std(self, A, b):
        W = np.where(self.live[:, None],
                     A / np.where(self.live, self.std, 1.0)[:, None], 0.0)
        return W, b - self.mean @ W


def _start(y: np.ndarray, d: int, C: int):
    prior = np.maximum(np.bincount(y, minlength=C), 0.5) / len(y)
    b = np.log(prior)
    return np.zeros((d, C)), b - b.mean()


def fit_softmax(X: np.ndarray, y: np.ndarray, reg_param: float,
                num_classes: int, precision: str = "f64",
                start: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                max_iter: int = 60, cg_iter: int = 80,
                tol: float = 1e-10) -> Dict[str, Any]:
    """Multinomial logistic regression at its optimum: minimise
    ``mean(cross-entropy) + reg_param / 2 * |A|^2`` over the (d, C)
    coefficients ``A`` of the standardised features and C free intercepts,
    by Newton steps in float64 whose direction comes from conjugate
    gradients preconditioned by the curvature's diagonal (run to a
    hundredth of the gradient, the curvature products in float32: the
    direction sets how fast the steps close in, not where they end), each
    step halved until the loss does not rise. Stops when no gradient entry
    is above ``tol``. Returns ``W`` (d, C) and ``b`` (C,) in the features' own scale
    with the class mean taken out of both, the deviations ``std``, the
    ``iterations`` taken and the last ``grad_max``.

    ``start`` is a (W, b) to start from: the optimum is unique (up to the
    class mean), so where the fit converges the start does not show.
    ``precision="bf16"`` is the control: the standardised features and every
    per-row temporary (margin, probability, residual, curvature products)
    are rounded to bfloat16 before each reduction, for ``max_iter`` steps."""
    q = reference._round_like(precision)
    exact = precision == "f64"
    n, d = X.shape
    C = int(num_classes)
    y = np.asarray(y).astype(np.int64)
    st = _Std(X, precision)
    Xs = st.Xs
    Xs32 = Xs.astype(np.float32) if exact else None
    Y = np.zeros((n, C))
    Y[np.arange(n), y] = 1.0
    A, b = _start(y, d, C) if start is None else st.to_std(*start)
    l2 = float(reg_param)

    def parts(A, b):
        Z = q(Xs @ q(A) + b)
        zmax = Z.max(axis=1, keepdims=True)
        lse = zmax[:, 0] + np.log(np.exp(Z - zmax).sum(axis=1))
        loss = float(np.mean(lse - Z[np.arange(n), y])
                     + 0.5 * l2 * (A * A).sum())
        return q(_softmax(Z)), loss

    P, loss = parts(A, b)
    it, gmax = 0, float("inf")
    for it in range(1, max_iter + 1):
        R = q(P - Y)
        gA = np.where(st.live[:, None], Xs.T @ R / n + l2 * A, 0.0)
        gb = R.mean(axis=0)
        gmax = float(max(np.abs(gA).max(), np.abs(gb).max()))
        if exact and gmax < tol:
            break
        S = q(P * (1.0 - P))
        dA = np.where(st.live[:, None], (Xs * Xs).T @ S / n + l2, 1.0)
        db = np.maximum(S.mean(axis=0), 1e-300)

        if exact:
            P32 = P.astype(np.float32)

            def hv(VA, vb):
                U = Xs32 @ VA.astype(np.float32) + vb.astype(np.float32)
                T = P32 * (U - (P32 * U).sum(axis=1, keepdims=True))
                return (np.where(st.live[:, None], (Xs32.T @ T).astype(
                    np.float64) / n + l2 * VA, 0.0),
                    T.sum(axis=0, dtype=np.float64) / n)
        else:
            def hv(VA, vb):
                U = q(Xs @ q(VA) + vb)
                T = q(P * (U - (P * U).sum(axis=1, keepdims=True)))
                return (np.where(st.live[:, None], Xs.T @ T / n + l2 * VA,
                                 0.0), T.mean(axis=0))

        # preconditioned conjugate gradients on H [sA; sb] = [gA; gb]
        sA, sb = np.zeros_like(A), np.zeros_like(b)
        rA, rb = gA.copy(), gb.copy()
        zA, zb = rA / dA, rb / db
        pA, pb = zA.copy(), zb.copy()
        rz = float((rA * zA).sum() + rb @ zb)
        r0 = np.sqrt(float((rA * rA).sum() + rb @ rb))
        for _ in range(cg_iter):
            hA, hb = hv(pA, pb)
            pHp = float((pA * hA).sum() + pb @ hb)
            if pHp <= 0:
                break
            alpha = rz / pHp
            sA += alpha * pA
            sb += alpha * pb
            rA -= alpha * hA
            rb -= alpha * hb
            if np.sqrt(float((rA * rA).sum() + rb @ rb)) < 1e-2 * r0:
                break
            zA, zb = rA / dA, rb / db
            rz_new = float((rA * zA).sum() + rb @ zb)
            pA = zA + (rz_new / rz) * pA
            pb = zb + (rz_new / rz) * pb
            rz = rz_new
        t = 1.0
        while True:
            A1, b1 = A - t * sA, b - t * sb
            P1, loss1 = parts(A1, b1)
            if loss1 <= loss + 1e-13 or t < 1e-3 or not exact:
                break
            t *= 0.5
        A, b, P, loss = A1, b1 - (b1.mean() - b.mean()), P1, loss1
    W, b0 = st.from_std(A, b)
    W, b0 = centred(W, b0)
    return {"W": W, "b": b0, "std": st.std, "iterations": it,
            "grad_max": gmax, "loss": loss}


def adam_softmax(X: np.ndarray, y: np.ndarray, reg_param: float,
                 num_classes: int, steps: int = 200, lr: float = 0.1
                 ) -> Dict[str, Any]:
    """The schedule the program's softmax fit had before PR 26: full-batch
    Adam from zero, ``steps`` steps at rate ``lr`` (beta 0.9 / 0.999), on
    the same objective. A control: the limits of the refit numbers must
    fail it."""
    n, d = X.shape
    C = int(num_classes)
    y = np.asarray(y).astype(np.int64)
    st = _Std(X, "f64")
    Xs = st.Xs.astype(np.float32)
    Y = np.zeros((n, C), dtype=np.float32)
    Y[np.arange(n), y] = 1.0
    A, b = np.zeros((d, C)), np.zeros(C)
    mA, vA, mb, vb = (np.zeros_like(A), np.zeros_like(A), np.zeros_like(b),
                      np.zeros_like(b))
    b1, b2, eps = 0.9, 0.999, 1e-8
    for i in range(1, int(steps) + 1):
        R = _softmax((Xs @ A.astype(np.float32) + b.astype(np.float32)
                      ).astype(np.float32)) - Y
        gA = np.where(st.live[:, None],
                      (Xs.T @ R).astype(np.float64) / n
                      + float(reg_param) * A, 0.0)
        gb = R.sum(axis=0, dtype=np.float64) / n
        mA, mb = b1 * mA + (1 - b1) * gA, b1 * mb + (1 - b1) * gb
        vA, vb = b2 * vA + (1 - b2) * gA * gA, b2 * vb + (1 - b2) * gb * gb
        A = A - lr * (mA / (1 - b1 ** i)) / (np.sqrt(vA / (1 - b2 ** i)) + eps)
        b = b - lr * (mb / (1 - b1 ** i)) / (np.sqrt(vb / (1 - b2 ** i)) + eps)
    W, b0 = centred(*st.from_std(A, b))
    return {"W": W, "b": b0, "std": st.std, "iterations": int(steps)}


def fit_elastic_net(X: np.ndarray, y: np.ndarray, reg_param: float,
                    elastic_net: float, num_classes: int,
                    steps: int = 20000, tol: float = 1e-12) -> Dict[str, Any]:
    """``mean(cross-entropy) + reg * (alpha |A|_1 + (1 - alpha) / 2 |A|^2)``
    by proximal-gradient steps (FISTA, restarted when the loss rises; step
    1 / L with L the bound ``lambda_max(Xs'Xs / n) / 2 + l2``). For tests at
    small size."""
    n, d = X.shape
    C = int(num_classes)
    y = np.asarray(y).astype(np.int64)
    st = _Std(X, "f64")
    Xs = np.c_[st.Xs, np.ones(n)]
    Y = np.zeros((n, C))
    Y[np.arange(n), y] = 1.0
    l1 = float(reg_param) * float(elastic_net)
    l2 = float(reg_param) * (1.0 - float(elastic_net))
    pen = np.r_[np.ones(d), 0.0][:, None]        # the intercept row is free
    L = 0.5 * float(np.linalg.eigvalsh(Xs.T @ Xs / n)[-1]) + l2
    T0 = np.zeros((d + 1, C))
    T0[d] = _start(y, d, C)[1]

    def smooth(T):
        Z = Xs @ T
        zmax = Z.max(axis=1, keepdims=True)
        lse = zmax[:, 0] + np.log(np.exp(Z - zmax).sum(axis=1))
        f = float(np.mean(lse - Z[np.arange(n), y])
                  + 0.5 * l2 * (pen * T * T).sum())
        return f, Xs.T @ (_softmax(Z) - Y) / n + l2 * pen * T

    def total(T, f):
        return f + l1 * float(np.abs(pen * T).sum())

    T, V, tk = T0.copy(), T0.copy(), 1.0
    f_T = total(T, smooth(T)[0])
    for it in range(1, int(steps) + 1):
        _, g = smooth(V)
        U = V - g / L
        T1 = np.where(pen > 0, np.sign(U) * np.maximum(np.abs(U) - l1 / L, 0.0),
                      U)
        f1 = total(T1, smooth(T1)[0])
        if f1 > f_T:                         # restart the momentum
            V, tk = T.copy(), 1.0
            continue
        t1 = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * tk * tk))
        V = T1 + (tk - 1.0) / t1 * (T1 - T)
        moved = float(np.abs(T1 - T).max())
        T, tk, f_T = T1, t1, f1
        if moved < tol:
            break
    W, b0 = st.from_std(T[:d], T[d])
    return {"W": W, "b": b0 - b0.mean(), "std": st.std, "iterations": it,
            "loss": f_T}


def cv_f1(X: np.ndarray, y: np.ndarray, reg_param: float, num_classes: int,
          folds: int, seed: int,
          start: Optional[Tuple[np.ndarray, np.ndarray]] = None,
          precision: str = "f64") -> float:
    """Mean over ``folds`` folds of the weighted F1, on a fold's rows, of
    the softmax fit on the others. The folds are the reference's own (every
    ``folds``-th row of a permutation drawn from ``seed``), every row of a
    fold is scored, and the fits stop at a gradient of 1e-4: the F1 reads
    the largest probability only."""
    n = X.shape[0]
    perm = np.random.default_rng([int(seed), 7]).permutation(n)
    out = []
    for f in range(int(folds)):
        val = np.zeros(n, dtype=bool)
        val[perm[f::int(folds)]] = True
        fit = fit_softmax(X[~val], y[~val], reg_param, num_classes,
                          precision, start,
                          max_iter=4 if precision != "f64" else 30, tol=1e-4)
        prob = softmax_prob(X[val], fit["W"], fit["b"],
                            "f64" if precision == "f64" else precision)
        out.append(weighted_f1(prob.argmax(axis=1), y[val], num_classes))
    return float(np.mean(out))
