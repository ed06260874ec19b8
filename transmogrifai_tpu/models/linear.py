"""Linear model families: logistic regression, linear/ridge regression,
linear SVC, naive Bayes.

TPU-native replacements for the reference's SparkML wrappers
(reference: core/.../impl/classification/OpLogisticRegression.scala,
OpLinearSVC.scala, OpNaiveBayes.scala, impl/regression/OpLinearRegression.scala).
Each family fits its whole hyperparameter × fold batch in ONE jitted, vmapped
XLA program: the inner loop is prox-Newton / closed-form solves built from
(n,d)ᵀ(n,d) MXU matmuls, and per-configuration 0/1 row-weight vectors express
CV folds without reshaping data.

Conventions (matching Spark ML so reference grids transfer):
* objective = mean loss + regParam * (α·‖w‖₁ + (1-α)/2·‖w‖₂²), bias unpenalized
* features are standardized internally (Spark standardization=true default);
  coefficients are reported in the original scale.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .api import FittedParams, ModelFamily, register_family

_PREC = jax.lax.Precision.HIGHEST


def _standardize(X: jnp.ndarray, w: jnp.ndarray):
    """Weighted feature standardization; returns (Xs, mean, scale).

    Columns constant within the weighted rows get a huge scale (Xs ≈ 0,
    coefficient pinned at 0) instead of 1/sqrt(noise) — same dead-column
    guard as _BatchStd, or the unscale step amplifies rounding noise 1e6x."""
    cnt = jnp.maximum(w.sum(), 1.0)
    mean = (X * w[:, None]).sum(0) / cnt
    var = ((X - mean) ** 2 * w[:, None]).sum(0) / cnt
    # dead = EXACTLY constant within the weighted rows (weighted range 0) —
    # matches Spark zeroing only zero-variance columns. An informative column
    # whose natural scale is tiny (std 1e-4 → var 1e-8) or whose offset is
    # huge (epoch-millis: var/ex2 ~ 1e-10) must NOT be pinned to 0, so no
    # variance threshold can be used here; the range test is exact
    active = w[:, None] > 0
    hi = jnp.where(active, X, -jnp.inf).max(0)
    lo = jnp.where(active, X, jnp.inf).min(0)
    dead = hi <= lo
    scale = jnp.where(dead, 1e30, jnp.sqrt(jnp.maximum(var, 1e-30)))
    return (X - mean) / scale, mean, scale


def _unscale(coef_s: jnp.ndarray, bias_s: jnp.ndarray, mean: jnp.ndarray,
             scale: jnp.ndarray):
    coef = coef_s / scale
    bias = bias_s - (coef * mean).sum()
    return coef, bias


# ---------------------------------------------------------------------------
# Binary logistic regression — batched prox-Newton-CG
#
# The whole |grid| × |folds| batch is ONE program in which every heavy op is a
# shared (n,d)@(d,B) matmul over the raw feature matrix: per-configuration
# standardization is folded into coefficient algebra (Xs·v computed as
# X·(v/scale) − mean·(v/scale)), so X is read once per matmul instead of being
# re-materialized per configuration, and the Newton direction comes from a
# fixed-length conjugate-gradient solve whose Hessian-vector products are two
# such matmuls (the LIBLINEAR trust-region-Newton structure, batched). This is
# the MXU-shaped replacement for the reference's per-config SparkML fits
# (OpValidator.scala:270-322).
# ---------------------------------------------------------------------------

class _BatchStd:
    """Per-config standardization algebra over shared matmuls.

    Globally standardizes X once (keeps the shared matmuls well-conditioned
    at fast default matmul precision whatever the raw column scales), then
    expresses each config's weighted standardization algebraically:
    Xs·v = Xg·(v/scale) − mean·(v/scale). The per-config standardized space —
    and hence Spark's regularization semantics (standardization=true) — is
    invariant to the global affine map. X is never copied per config."""

    def __init__(self, X, W):
        g_mean = X.mean(axis=0)
        g_scale = jnp.sqrt(jnp.maximum(X.var(axis=0), 1e-12))
        self.g_mean, self.g_scale = g_mean, g_scale
        self.Xg = (X - g_mean) / g_scale
        self.Wt = W.T                                        # (n, B)
        self.cnt = jnp.maximum(W.sum(axis=1), 1.0)           # (B,)
        mean = (self.Wt.T @ self.Xg) / self.cnt[:, None]     # (B, d)
        ex2 = (self.Wt.T @ (self.Xg * self.Xg)) / self.cnt[:, None]
        var_raw = ex2 - mean ** 2
        self.var = jnp.maximum(var_raw, 1e-12)
        # a column that is CONSTANT within a config's weighted rows (e.g. a
        # rare one-hot slot whose nonzero rows all fell in the val fold) has
        # var ≈ rounding noise; 1/sqrt(var) then blows the solve up to NaN.
        # Give dead columns a huge scale instead: Xs ≈ 0, gradient 0, coef
        # stays 0 — Spark's zero-variance standardization semantics. The
        # test is RELATIVE to ex2 (one-pass cancellation noise is eps·ex2,
        # eps≈6e-8 f32) so a genuinely tiny-but-varying column stays alive;
        # the absolute floor catches columns constant at ≈0 within the config
        dead = var_raw < jnp.maximum(1e-6 * ex2, 1e-10)
        self.mean = mean
        self.scale = jnp.where(dead, 1e30, jnp.sqrt(self.var))  # (B, d)

    def xs_dot(self, A):
        """Xs Aᵀ for A (B, d) → (n, B)."""
        At = A / self.scale
        return self.Xg @ At.T - (self.mean * At).sum(axis=1)[None, :]

    def xs_t_dot(self, V):
        """Xsᵀ V for V (n, B) → (B, d)."""
        return ((V.T @ self.Xg)
                - V.sum(axis=0)[:, None] * self.mean) / self.scale

    def unscale(self, A, b):
        """Per-config standardized coefficients → original scale."""
        coef_g = A / self.scale
        bias_g = b - (coef_g * self.mean).sum(axis=1)
        coef = coef_g / self.g_scale
        bias = bias_g - (coef * self.g_mean).sum(axis=1)
        return coef, bias

    def typed_ops(self, cdt, Xg_c):
        """(xs_dot_c, xs_t_dot_c) computing the standardized matmuls with
        (n, B) intermediates in ``cdt`` (bf16 for CV sweeps) while every
        REDUCTION accumulates f32. ``Xg_c`` is the pre-cast globally
        standardized matrix so callers share one cast."""
        def xs_dot_c(A):
            """Xs Aᵀ → (n, B) cdt."""
            At = (A / self.scale).astype(cdt)
            off = (self.mean * (A / self.scale)).sum(axis=1).astype(cdt)
            return (jnp.dot(Xg_c, At.T, preferred_element_type=cdt)
                    - off[None, :])

        def xs_t_dot_c(V):
            """Xsᵀ V for V (n, B) cdt → (B, d) f32 (f32 accumulate)."""
            vt = jnp.dot(V.T, Xg_c, preferred_element_type=jnp.float32)
            return (vt - jnp.sum(V, axis=0, dtype=jnp.float32)[:, None]
                    * self.mean) / self.scale

        return xs_dot_c, xs_t_dot_c


@partial(jax.jit, static_argnames=("newton_iters", "cg_iters", "sweep"))
def _fit_logreg_batch(X, y, W, reg, elastic_net, newton_iters=10, cg_iters=8,
                      sweep=False):
    """Fit B logistic regressions at once. W: (B, n) per-config row weights;
    reg/elastic_net: (B,). Returns (coef (B, d), bias (B,)) in original scale.

    ``sweep``: keep the (n, B) elementwise temps (Z/P/R/S and the CG
    Hessian-vector products) in bfloat16 — the fit is HBM-bound on those
    temps at 1M rows, and CV candidates only need metric-ranking accuracy;
    all gradient/Hessian REDUCTIONS still accumulate f32, and the winner's
    refit runs with sweep=False (exact f32 temps).
    """
    nB = W.shape[0]
    d = X.shape[1]
    std = _BatchStd(X, W)
    Xg, Wt, cnt = std.Xg, std.Wt, std.cnt
    mean, var, scale = std.mean, std.var, std.scale
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net
    cdt = jnp.bfloat16 if sweep else X.dtype
    Xg_c = Xg.astype(cdt)
    Wt_c = Wt.astype(cdt)
    yv_c = y[:, None].astype(cdt)
    xs_dot_c, xs_t_dot_c = std.typed_ops(cdt, Xg_c)

    def newton_step(carry, _):
        A, b = carry                                    # (B, d), (B,)
        Z = xs_dot_c(A) + b[None, :].astype(cdt)        # (n, B) cdt
        P = jax.nn.sigmoid(Z)
        R = Wt_c * (P - yv_c)                           # (n, B) cdt
        S = Wt_c * jnp.maximum(P * (1 - P),
                               jnp.asarray(1e-6, cdt))  # (n, B) cdt
        g_A = xs_t_dot_c(R) / cnt[:, None] + l2[:, None] * A
        g_b = jnp.sum(R, axis=0, dtype=jnp.float32) / cnt
        ssum = jnp.sum(S, axis=0, dtype=jnp.float32)

        def hv(VA, vb):                                 # H·[v; v_b], all B
            U = xs_dot_c(VA) + vb[None, :].astype(cdt)
            T = S * U
            hA = xs_t_dot_c(T) / cnt[:, None] + (l2 + 1e-8)[:, None] * VA
            hb = jnp.sum(T, axis=0, dtype=jnp.float32) / cnt + 1e-8 * vb
            return hA, hb

        def cg_step(c, _):
            dA, db, rA, rb, pA, pb, rs = c
            hA, hb = hv(pA, pb)
            pHp = (pA * hA).sum(axis=1) + pb * hb
            alpha = rs / jnp.maximum(pHp, 1e-20)
            dA = dA + alpha[:, None] * pA
            db = db + alpha * pb
            rA = rA - alpha[:, None] * hA
            rb = rb - alpha * hb
            rs_new = (rA * rA).sum(axis=1) + rb * rb
            beta = rs_new / jnp.maximum(rs, 1e-20)
            pA = rA + beta[:, None] * pA
            pb = rb + beta * pb
            return (dA, db, rA, rb, pA, pb, rs_new), None

        z0 = jnp.zeros_like(A)
        zb = jnp.zeros_like(b)
        rs0 = (g_A * g_A).sum(axis=1) + g_b * g_b
        (dA, db, *_), _ = jax.lax.scan(
            cg_step, (z0, zb, g_A, g_b, g_A, g_b, rs0), None, length=cg_iters)

        A = A - dA
        b = b - db
        # prox for L1 in the diagonal-Hessian metric:
        # diag(Hs) = (Sᵀ Xg² − 2 mean·(Sᵀ Xg) + Σ S·mean²) / var / cnt
        StX = jnp.dot(S.T, Xg_c, preferred_element_type=jnp.float32)
        StX2 = jnp.dot(S.T, Xg_c * Xg_c,
                       preferred_element_type=jnp.float32)
        diag = (StX2 - 2 * mean * StX
                + ssum[:, None] * mean ** 2) / var / cnt[:, None]
        thresh = l1[:, None] / jnp.maximum(diag, 1e-8)
        A = jnp.where(l1[:, None] > 0,
                      jnp.sign(A) * jnp.maximum(jnp.abs(A) - thresh, 0.0), A)
        return (A, b), None

    A0 = jnp.zeros((nB, d), X.dtype)
    b0 = jnp.zeros((nB,), X.dtype)
    with jax.named_scope("linear.newton_cg"):
        (A, b), _ = jax.lax.scan(newton_step, (A0, b0), None,
                                 length=newton_iters)
    return std.unscale(A, b)


def _fit_logreg(X, y, w, reg, elastic_net):
    """Single-config fit: the B=1 slice of the batched solver."""
    coef, bias = _fit_logreg_batch(
        X, y, w[None, :], jnp.asarray([reg], X.dtype),
        jnp.asarray([elastic_net], X.dtype))
    return coef[0], bias[0]


class LogisticRegressionFamily(ModelFamily):
    """reference OpLogisticRegression (defaults: regParam [0.01,0.1,0.2],
    elasticNetParam [0,0.5] — DefaultSelectorParams.scala)."""

    name = "OpLogisticRegression"
    #: grid values are consumed purely as (B,) arrays — safe to
    #: trace as a packed, donated device block under the mesh
    traced_grid_ok = True
    supports = frozenset({"binary", "multiclass"})

    def default_grid(self, problem: str) -> List[Dict[str, Any]]:
        return [{"regParam": r, "elasticNetParam": e}
                for r in (0.01, 0.1, 0.2) for e in (0.0, 0.5)]

    def fit_batch(self, X, y, weights, grid, num_classes):
        if num_classes <= 2:
            coef, bias = _fit_logreg_batch(
                X, y, weights, grid["regParam"], grid["elasticNetParam"])
            return {"coef": coef, "bias": bias}
        W, b = _fit_softmax_batch(X, y.astype(jnp.int32), weights,
                                  grid["regParam"], num_classes)
        return {"W": W, "b": b}

    def sweep_fit_batch(self, X, y, weights, grid, num_classes):
        # CV candidates: bf16 (n, B) temps and a shorter Newton-CG schedule
        # — metric-ranking accuracy only; the winner refits through
        # fit_batch (exact f32 temps, full 10x8 schedule)
        if num_classes <= 2:
            coef, bias = _fit_logreg_batch(
                X, y, weights, grid["regParam"], grid["elasticNetParam"],
                newton_iters=8, cg_iters=6, sweep=True)
            return {"coef": coef, "bias": bias}
        return self.fit_batch(X, y, weights, grid, num_classes)

    def predict_batch(self, params, X, num_classes):
        if num_classes <= 2:
            return jax.nn.sigmoid(
                jnp.einsum("bd,nd->bn", params["coef"], X, precision=_PREC)
                + params["bias"][:, None])
        logits = jnp.einsum("bdc,nd->bnc", params["W"], X, precision=_PREC) \
            + params["b"][:, None, :]
        return jax.nn.softmax(logits, axis=-1)

    def predict_parts(self, fitted: FittedParams, X):
        if fitted.num_classes <= 2:
            margin = X @ jnp.asarray(fitted.params["coef"]) \
                + fitted.params["bias"]
            p1 = jax.nn.sigmoid(margin)
            prob = jnp.stack([1 - p1, p1], axis=1)
            raw = jnp.stack([-margin, margin], axis=1)
        else:
            raw = X @ jnp.asarray(fitted.params["W"]) \
                + jnp.asarray(fitted.params["b"])
            prob = jax.nn.softmax(raw, axis=-1)
        pred = prob.argmax(axis=1).astype(jnp.float32)
        return {"prediction": pred, "probability": prob, "rawPrediction": raw}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


@partial(jax.jit, static_argnames=("num_classes", "iters"))
def _fit_softmax_batch(X, y_idx, W_rows, reg, num_classes, iters=200):
    """Multinomial logistic regression, all B configs in one program of
    shared matmuls: full-batch Adam whose forward/backward are single
    (n,d)@(d,B·C) / (d,n)@(n,B·C) contractions via the same standardization
    algebra as the binary solver. W_rows: (B, n) row weights; reg: (B,).
    Returns (W (B, d, C), b (B, C)) in original scale."""
    C = num_classes
    nB = W_rows.shape[0]
    d = X.shape[1]
    std = _BatchStd(X, W_rows)
    Xg, cnt = std.Xg, std.cnt
    mean, scale = std.mean, std.scale                   # (B, d)
    Wt = W_rows.T                                       # (n, B)
    Y = jax.nn.one_hot(y_idx, C, dtype=X.dtype)         # (n, C)

    def grads(Wc, b):
        """Wc: (B, d, C) per-config standardized coefs; b: (B, C)."""
        At = Wc / scale[:, :, None]                     # (B, d, C)
        off = (mean[:, :, None] * At).sum(axis=1)       # (B, C)
        Z = jnp.einsum("nd,bdc->nbc", Xg, At) + (b - off)[None]
        P = jax.nn.softmax(Z, axis=-1)
        R = Wt[:, :, None] * (P - Y[:, None, :])        # (n, B, C)
        GX = jnp.einsum("nd,nbc->bdc", Xg, R)           # Xgᵀ R
        Rsum = R.sum(axis=0)                            # (B, C)
        g_W = ((GX - mean[:, :, None] * Rsum[:, None, :]) / scale[:, :, None]
               / cnt[:, None, None]) + reg[:, None, None] * Wc
        g_b = Rsum / cnt[:, None]
        return g_W, g_b

    # hand-rolled Adam: a few lines here, no optimizer library needed
    lr, b1, b2, eps = 0.1, 0.9, 0.999, 1e-8
    params = (jnp.zeros((nB, d, C), X.dtype), jnp.zeros((nB, C), X.dtype))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def step(carry, i):
        params, m, v = carry
        g = grads(*params)
        m = jax.tree_util.tree_map(lambda a, b_: b1 * a + (1 - b1) * b_, m, g)
        v = jax.tree_util.tree_map(
            lambda a, b_: b2 * a + (1 - b2) * b_ * b_, v, g)
        t = i + 1.0
        params = jax.tree_util.tree_map(
            lambda p, mm, vv: p - lr * (mm / (1 - b1 ** t)) /
            (jnp.sqrt(vv / (1 - b2 ** t)) + eps), params, m, v)
        return (params, m, v), None

    (params, _, _), _ = jax.lax.scan(
        step, (params, zeros, zeros), jnp.arange(iters, dtype=X.dtype))
    Wc, b = params
    # per-config standardized → Xg space → original space (per class)
    W_g = Wc / scale[:, :, None]
    b_g = b - (W_g * mean[:, :, None]).sum(axis=1)
    Wx = W_g / std.g_scale[None, :, None]
    bx = b_g - (Wx * std.g_mean[None, :, None]).sum(axis=1)
    return Wx, bx


def _fit_softmax(X, y_idx, w, reg, num_classes, iters=200):
    """Single-config fit: the B=1 slice of the batched solver."""
    W, b = _fit_softmax_batch(X, y_idx, w[None, :],
                              jnp.asarray([reg], X.dtype), num_classes,
                              iters=iters)
    return W[0], b[0]


# ---------------------------------------------------------------------------
# Linear / ridge regression — closed form + ISTA refinement for L1
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("l1_iters",))
def _fit_linreg(X, y, w, reg, elastic_net, l1_iters=60):
    n, d = X.shape
    Xs, mean, scale = _standardize(X, w)
    cnt = jnp.maximum(w.sum(), 1.0)
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net
    Xa = jnp.concatenate([Xs, jnp.ones((n, 1), X.dtype)], axis=1)
    A = jnp.einsum("ni,nj->ij", Xa * w[:, None], Xa, precision=_PREC) / cnt
    A = A + jnp.diag(jnp.concatenate([jnp.full((d,), l2), jnp.zeros((1,))])) \
        + 1e-8 * jnp.eye(d + 1, dtype=X.dtype)
    rhs = (Xa * (w * y)[:, None]).sum(0) / cnt
    theta = jnp.linalg.solve(A, rhs)

    # ISTA refinement handles the L1 part (no-op when l1 == 0)
    lips = jnp.trace(A)  # cheap Lipschitz upper bound for the quadratic part
    step_sz = 1.0 / jnp.maximum(lips, 1e-6)

    def ista(theta, _):
        grad = A @ theta - rhs
        t = theta - step_sz * grad
        coef = jnp.sign(t[:d]) * jnp.maximum(jnp.abs(t[:d]) - step_sz * l1, 0.0)
        return jnp.concatenate([coef, t[d:]]), None

    theta = jax.lax.cond(
        l1 > 0,
        lambda th: jax.lax.scan(ista, th, None, length=l1_iters)[0],
        lambda th: th, theta)
    coef, bias = _unscale(theta[:d], theta[d], mean, scale)
    return coef, bias


_fit_linreg_batch = jax.jit(jax.vmap(_fit_linreg, in_axes=(None, None, 0, 0, 0)))


class LinearRegressionFamily(ModelFamily):
    """reference OpLinearRegression (defaults: regParam [0.001,0.01,0.1],
    elasticNetParam [0,0.5])."""

    name = "OpLinearRegression"
    #: grid values are consumed purely as (B,) arrays — safe to
    #: trace as a packed, donated device block under the mesh
    traced_grid_ok = True
    supports = frozenset({"regression"})

    def default_grid(self, problem: str) -> List[Dict[str, Any]]:
        return [{"regParam": r, "elasticNetParam": e}
                for r in (0.001, 0.01, 0.1) for e in (0.0, 0.5)]

    def fit_batch(self, X, y, weights, grid, num_classes):
        coef, bias = _fit_linreg_batch(
            X, y, weights, grid["regParam"], grid["elasticNetParam"])
        return {"coef": coef, "bias": bias}

    def predict_batch(self, params, X, num_classes):
        return jnp.einsum("bd,nd->bn", params["coef"], X, precision=_PREC) \
            + params["bias"][:, None]

    def predict_parts(self, fitted: FittedParams, X):
        pred = X @ jnp.asarray(fitted.params["coef"]) + fitted.params["bias"]
        return {"prediction": pred}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


# ---------------------------------------------------------------------------
# Linear SVC — squared hinge + L2, Nesterov accelerated GD, batched over
# configs via the same shared-matmul standardization algebra as logistic.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "sweep"))
def _fit_svc_batch(X, y, W, reg, iters=100, sweep=False):
    """Fit B linear SVCs at once. W: (B, n) row weights; reg: (B,).
    Each GD step is two shared (n,d)@(d,B) matmuls. ``sweep``: bf16 (n, B)
    margin/gradient temps (f32 reduction accumulates) — see
    _fit_logreg_batch."""
    nB = W.shape[0]
    d = X.shape[1]
    std = _BatchStd(X, W)
    Wt, cnt = std.Wt, std.cnt
    cdt = jnp.bfloat16 if sweep else X.dtype
    Wt_c = Wt.astype(cdt)
    ypm_c = (2.0 * y - 1.0)[:, None].astype(cdt)        # (n, 1), {-1,+1}
    xs_dot_c, xs_t_dot_c = std.typed_ops(cdt, std.Xg.astype(cdt))

    def loss_grad(A, b):
        Z = xs_dot_c(A) + b[None, :].astype(cdt)
        M = ypm_c * Z                                   # (n, B) margins
        act = jnp.maximum(jnp.asarray(1.0, cdt) - M, jnp.asarray(0.0, cdt))
        G_m = jnp.asarray(-2.0, cdt) * act * ypm_c * Wt_c   # (n, B)
        g_A = xs_t_dot_c(G_m) / cnt[:, None] + reg[:, None] * A
        g_b = jnp.sum(G_m, axis=0, dtype=jnp.float32) / cnt
        return g_A, g_b

    # Lipschitz ≈ 2·mean row-norm² (+ reg); standardized rows → ‖x‖² ≈ d
    lr = 1.0 / (2.0 * d / 4.0 + reg + 1.0)              # (B,)

    def step(carry, _):
        A, b, Ap, bp, t = carry
        mom = (t - 1.0) / (t + 2.0)
        mA = A + mom * (A - Ap)
        mb = b + mom * (b - bp)
        g_A, g_b = loss_grad(mA, mb)
        return (mA - lr[:, None] * g_A, mb - lr * g_b, A, b, t + 1.0), None

    zA = jnp.zeros((nB, d), X.dtype)
    zb = jnp.zeros((nB,), X.dtype)
    (A, b, _, _, _), _ = jax.lax.scan(
        step, (zA, zb, zA, zb, jnp.asarray(1.0, X.dtype)), None, length=iters)
    return std.unscale(A, b)


def _fit_svc(X, y, w, reg, iters=100):
    """Single-config fit: the B=1 slice of the batched solver."""
    coef, bias = _fit_svc_batch(X, y, w[None, :], jnp.asarray([reg], X.dtype),
                                iters=iters)
    return coef[0], bias[0]


class LinearSVCFamily(ModelFamily):
    """reference OpLinearSVC (defaults: regParam [0.01,0.1,0.2])."""

    name = "OpLinearSVC"
    #: grid values are consumed purely as (B,) arrays — safe to
    #: trace as a packed, donated device block under the mesh
    traced_grid_ok = True
    supports = frozenset({"binary"})

    def default_grid(self, problem: str) -> List[Dict[str, Any]]:
        return [{"regParam": r} for r in (0.01, 0.1, 0.2)]

    def fit_batch(self, X, y, weights, grid, num_classes):
        coef, bias = _fit_svc_batch(X, y, weights, grid["regParam"])
        return {"coef": coef, "bias": bias}

    def sweep_fit_batch(self, X, y, weights, grid, num_classes):
        coef, bias = _fit_svc_batch(X, y, weights, grid["regParam"],
                                    sweep=True)
        return {"coef": coef, "bias": bias}

    def predict_batch(self, params, X, num_classes):
        # squash margins so threshold-style validation metrics (which cut at
        # 0.5) and LogLoss see [0,1] scores; rank metrics are unaffected by
        # the monotone map, and sigmoid(m) > 0.5 ⇔ margin > 0
        margins = jnp.einsum("bd,nd->bn", params["coef"], X, precision=_PREC) \
            + params["bias"][:, None]
        return jax.nn.sigmoid(margins)

    def predict_parts(self, fitted: FittedParams, X):
        margin = X @ jnp.asarray(fitted.params["coef"]) + fitted.params["bias"]
        pred = (margin > 0).astype(jnp.float32)
        raw = jnp.stack([-margin, margin], axis=1)
        return {"prediction": pred, "rawPrediction": raw}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


# ---------------------------------------------------------------------------
# Naive Bayes — multinomial with Laplace smoothing (closed-form counting)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_classes",))
def _fit_nb(X, y_idx, w, smoothing, num_classes):
    Xp = jnp.maximum(X, 0.0)  # multinomial NB needs nonnegative counts
    Y = jax.nn.one_hot(y_idx, num_classes, dtype=X.dtype) * w[:, None]
    class_cnt = Y.sum(0)
    feat_cnt = jnp.einsum("nc,nd->cd", Y, Xp, precision=_PREC)
    d = X.shape[1]
    log_prob = jnp.log(feat_cnt + smoothing) - \
        jnp.log(feat_cnt.sum(1, keepdims=True) + smoothing * d)
    log_prior = jnp.log(jnp.maximum(class_cnt, 1e-12) /
                        jnp.maximum(class_cnt.sum(), 1e-12))
    return log_prob, log_prior


_fit_nb_batch = jax.jit(jax.vmap(_fit_nb, in_axes=(None, None, 0, 0, None)),
                        static_argnames=("num_classes",))


class NaiveBayesFamily(ModelFamily):
    """reference OpNaiveBayes (default smoothing 1.0)."""

    name = "OpNaiveBayes"
    #: grid values are consumed purely as (B,) arrays — safe to
    #: trace as a packed, donated device block under the mesh
    traced_grid_ok = True
    supports = frozenset({"binary", "multiclass"})

    def default_grid(self, problem: str) -> List[Dict[str, Any]]:
        return [{"smoothing": s} for s in (0.5, 1.0, 2.0)]

    def fit_batch(self, X, y, weights, grid, num_classes):
        lp, prior = _fit_nb_batch(X, y.astype(jnp.int32), weights,
                                  grid["smoothing"], max(num_classes, 2))
        return {"log_prob": lp, "log_prior": prior}

    def predict_batch(self, params, X, num_classes):
        Xp = jnp.maximum(X, 0.0)
        logits = jnp.einsum("bcd,nd->bnc", params["log_prob"], Xp,
                            precision=_PREC) + params["log_prior"][:, None, :]
        if num_classes <= 2:
            return jax.nn.softmax(logits, axis=-1)[:, :, 1]
        return jax.nn.softmax(logits, axis=-1)

    def predict_parts(self, fitted: FittedParams, X):
        Xp = jnp.maximum(X, 0.0)
        raw = Xp @ jnp.asarray(fitted.params["log_prob"]).T \
            + jnp.asarray(fitted.params["log_prior"])
        prob = jax.nn.softmax(raw, axis=-1)
        pred = prob.argmax(axis=1).astype(jnp.float32)
        return {"prediction": pred, "probability": prob, "rawPrediction": raw}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


register_family(LogisticRegressionFamily())
register_family(LinearRegressionFamily())
register_family(LinearSVCFamily())
register_family(NaiveBayesFamily())
