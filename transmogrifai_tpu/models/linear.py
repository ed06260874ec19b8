"""Linear model families: logistic regression, linear/ridge regression,
linear SVC, naive Bayes.

TPU-native replacements for the reference's SparkML wrappers
(reference: core/.../impl/classification/OpLogisticRegression.scala,
OpLinearSVC.scala, OpNaiveBayes.scala, impl/regression/OpLinearRegression.scala).
Each family fits its whole hyperparameter × fold batch in ONE jitted XLA
program over the ONE shared feature matrix: the inner loop is prox-Newton /
moment-based least-squares solves built from (n,d)ᵀ(n,d) MXU matmuls, lanes
appear only in (n, lanes) or (lanes, d, d) operands, and per-configuration
0/1 row-weight vectors express CV folds without reshaping data.

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

    def __init__(self, X, W, precision=None):
        g_mean = X.mean(axis=0)
        g_scale = jnp.sqrt(jnp.maximum(X.var(axis=0), 1e-12))
        self.g_mean, self.g_scale = g_mean, g_scale
        self.Xg = (X - g_mean) / g_scale
        self.Wt = W.T                                        # (n, B)
        self.cnt = jnp.maximum(W.sum(axis=1), 1.0)           # (B,)
        mean = jnp.matmul(self.Wt.T, self.Xg,
                          precision=precision) / self.cnt[:, None]  # (B, d)
        ex2 = jnp.matmul(self.Wt.T, self.Xg * self.Xg,
                         precision=precision) / self.cnt[:, None]
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


#: (Newton steps, CG steps a Newton step) of the binary fit: a sweep
#: candidate's shorter schedule, the refit's full one
_LOGREG_STEPS = {True: (8, 6), False: (10, 8)}


def logreg_matrix_passes(sweep: bool) -> int:
    """Reads of the (rows, features) matrix in the program the chip's
    compiler makes of `_fit_logreg_batch` (counted in ``as_text()`` for a
    described v5e; tests/test_device_names_tpu.py holds it to that count).
    A sweep's lanes in bfloat16: five to standardise and cast
    (`_BatchStd`), then a Newton step's margin, gradient and two
    diagonal-curvature products and two products a CG step. The refit's
    one float32 lane: four before the loop, and the gradient and the two
    curvature products of a Newton step are ONE fusion that reads the
    matrix and its stored square, so three reads a step beside the CG's.
    At a cell's size the compiler lays the matrix out anew first (a
    ``copy``): one read more."""
    newton, cg = _LOGREG_STEPS[bool(sweep)]
    if sweep:
        return 5 + newton * (4 + 2 * cg)
    return 4 + newton * (3 + 2 * cg)


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
            newton, cg = _LOGREG_STEPS[False]
            coef, bias = _fit_logreg_batch(
                X, y, weights, grid["regParam"], grid["elasticNetParam"],
                newton_iters=newton, cg_iters=cg)
            return {"coef": coef, "bias": bias}
        return self._fit_softmax(X, y, weights, grid, num_classes, False)

    @staticmethod
    def _fit_softmax(X, y, weights, grid, num_classes, sweep):
        W, b = _fit_softmax_batch(X, y.astype(jnp.int32), weights,
                                  grid["regParam"], grid["elasticNetParam"],
                                  num_classes, sweep=sweep)
        return {"W": W, "b": b}

    def sweep_fit_batch(self, X, y, weights, grid, num_classes):
        # CV candidates: bf16 (n, B) temps and a shorter Newton-CG schedule
        # — metric-ranking accuracy only; the winner refits through
        # fit_batch (exact f32 temps, full 10x8 schedule)
        if num_classes <= 2:
            newton, cg = _LOGREG_STEPS[True]
            coef, bias = _fit_logreg_batch(
                X, y, weights, grid["regParam"], grid["elasticNetParam"],
                newton_iters=newton, cg_iters=cg, sweep=True)
            return {"coef": coef, "bias": bias}
        return self._fit_softmax(X, y, weights, grid, num_classes, True)

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        if num_classes <= 2:
            return {"matrixPasses": logreg_matrix_passes(sweep)}
        chunk = softmax_lane_chunk(rows, len(grid), num_classes)
        return {"contractions": softmax_contractions(sweep),
                "laneChunks": -(-len(grid) // chunk)}

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
            # a real (n,d)@(d,C) product: at the chip's default precision
            # its bfloat16 passes move a probability by 1e-2 (PR 26)
            raw = jnp.dot(X, jnp.asarray(fitted.params["W"]),
                          precision=_PREC) + jnp.asarray(fitted.params["b"])
            prob = jax.nn.softmax(raw, axis=-1)
        pred = prob.argmax(axis=1).astype(jnp.float32)
        return {"prediction": pred, "probability": prob, "rawPrediction": raw}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


# ---------------------------------------------------------------------------
# Multinomial logistic regression — batched orthant-wise Newton-CG
#
# Built as the binary solver above: every heavy op is one of the two shared
# contractions (n,d)@(d,B·C) and (d,n)@(n,B·C) over the globally standardized
# matrix, per-lane standardization is coefficient algebra (_BatchStd), the
# Newton direction comes from a fixed-length conjugate-gradient solve. What
# C classes add:
# * the curvature of a rare class's free intercept is its share of the rows
#   (1e-5 here and there), so the solve is preconditioned by the Hessian's
#   diagonal (two more contractions a Newton step), and the intercepts start
#   at the log class shares, where every Newton step is a small one;
# * the binary solver's L1 step (soft threshold in the diagonal metric after
#   the Newton step) over-shrinks where that diagonal is a rare class's, so
#   the L1 term is handled orthant-wise with two metrics: coordinates off
#   zero take the Newton-CG step on the pseudo-gradient together and stop at
#   zero where they would change sign; one AT zero whose gradient exceeds l1
#   leaves by its own diagonal step. Its fixed point is the elastic-net
#   optimum whatever the Hessian's off-diagonal;
# * a step is tried at 1, 1/4 and 1/16 of its length (one contraction each)
#   and the first that does not raise the objective is taken: orthant
#   projections make full Newton steps cycle at small penalties.
# ---------------------------------------------------------------------------

#: element budget of ONE (rows, lanes, classes) temporary of the softmax
#: solver (a lane is one grid point on one fold): lanes are batched until
#: rows·lanes·classes reaches it, then ``lax.map`` runs the chunks of lanes
#: one after another, as the tree growers chunk their configurations
_SOFTMAX_LANE_ELEMS = 1 << 28

#: (Newton steps, conjugate-gradient steps per Newton step): the refit's
#: schedule (float32 temporaries) and the sweep's (bfloat16 temporaries).
#: An L2 fit is at its float32 floor after 6 Newton steps; a weak L1 term
#: (regParam 0.01, elasticNetParam 0.5) needs some 20 to settle which
#: coordinates are zero, and the refit is one lane, so it gets 24
_SOFTMAX_SCHEDULE = {False: (24, 12), True: (8, 8)}

#: step lengths tried, longest first
_SOFTMAX_STEPS = (1.0, 0.25, 0.0625)


def softmax_lane_chunk(rows: int, lanes: int, num_classes: int) -> int:
    """Lanes per chunk under ``_SOFTMAX_LANE_ELEMS``, the chunks evened out
    (18 lanes under a budget of 12 run as 9 + 9, not 12 + 6)."""
    cap = max(1, _SOFTMAX_LANE_ELEMS // max(1, rows * num_classes))
    n_chunks = -(-lanes // min(cap, lanes))
    return -(-lanes // n_chunks)


def softmax_contractions(sweep: bool) -> int:
    """How many (n,d)x(d,lanes·C) products one softmax fit runs per chunk
    of lanes: the starting objective, then per Newton step the margins, the
    gradient, two for the Hessian's diagonal, two per conjugate-gradient
    step and one per step length tried."""
    newton, cg = _SOFTMAX_SCHEDULE[bool(sweep)]
    return 1 + newton * (4 + 2 * cg + len(_SOFTMAX_STEPS))


@partial(jax.jit, static_argnames=("num_classes", "sweep", "lane_chunk"))
def _fit_softmax_batch(X, y_idx, W_rows, reg, elastic_net, num_classes,
                       sweep=False, lane_chunk=None):
    """Multinomial logistic regression, B lanes at once. W_rows: (B, n) row
    weights; reg/elastic_net: (B,). Returns (W (B, d, C), b (B, C)) in
    original scale, the intercepts centred over the classes.

    ``sweep``: keep the (n, lanes, C) temporaries in bfloat16 and run the
    shorter schedule (CV candidates need metric-ranking accuracy; every
    reduction still accumulates f32); the winner's refit runs with
    sweep=False, its contractions at full float32 precision.
    ``lane_chunk``: lanes per chunk (default: what ``_SOFTMAX_LANE_ELEMS``
    allows); each lane's fit is its own, so the result does not depend on
    it."""
    C = num_classes
    nB, n = W_rows.shape
    d = X.shape[1]
    newton_iters, cg_iters = _SOFTMAX_SCHEDULE[bool(sweep)]
    prec = None if sweep else _PREC
    std = _BatchStd(X, W_rows, precision=prec)
    cdt = jnp.bfloat16 if sweep else X.dtype
    f32 = jnp.float32
    # an objective may rise by this share and the step still be taken: the
    # rounding of the objective's own sum, below which steps are Newton's
    slack = 1e-3 if sweep else 1e-6
    Xg_c = std.Xg.astype(cdt)
    Xg2_c = Xg_c * Xg_c
    Y_c = jax.nn.one_hot(y_idx, C, dtype=cdt)               # (n, C)

    def one_chunk(lane):
        W_c, mean, scale, var, cnt, l1, l2 = lane           # leading axis cb
        cb = W_c.shape[0]
        Wt_c = W_c.T.astype(cdt)[:, :, None]                # (n, cb, 1)
        inv = (1.0 / scale)[:, :, None]                     # (cb, d, 1)
        m3 = mean[:, :, None]
        cnt3, l1_3, l2_3 = (v[:, None, None] for v in (cnt, l1, l2))

        def xs_dot(A, b):
            """Xs·A + b for A (cb, d, C), b (cb, C) -> (n, cb, C) cdt."""
            At = A * inv
            off = b - (m3 * At).sum(axis=1)                 # (cb, C)
            Z = jnp.dot(Xg_c, At.transpose(1, 0, 2).reshape(d, cb * C)
                        .astype(cdt), preferred_element_type=cdt,
                        precision=prec)
            return Z.reshape(n, cb, C) + off[None].astype(cdt)

        def xt_dot(V, Xc):
            """Xcᵀ·V for V (n, cb, C) cdt -> (cb, d, C) f32."""
            vt = jnp.dot(V.reshape(n, cb * C).T, Xc,
                         preferred_element_type=f32, precision=prec)
            return vt.reshape(cb, C, d).transpose(0, 2, 1)

        def xs_t_dot(V):
            """Xsᵀ·V -> (cb, d, C) f32, and V's column sums (cb, C)."""
            vsum = jnp.sum(V, axis=0, dtype=f32)
            return (xt_dot(V, Xg_c) - m3 * vsum[:, None, :]) * inv, vsum

        def zero_mean(vb):               # the softmax's null direction out
            return vb - vb.mean(axis=1, keepdims=True)

        def objective(A, b):
            """(cb,) mean cross-entropy + penalty."""
            Z = xs_dot(A, b).astype(f32)
            ce = jax.nn.logsumexp(Z, axis=-1) - (Z * Y_c[:, None, :]
                                                 ).sum(axis=-1)
            return ((Wt_c[:, :, 0] * ce).sum(axis=0, dtype=f32) / cnt
                    + (0.5 * l2_3 * A * A + l1_3 * jnp.abs(A)
                       ).sum(axis=(1, 2)))

        def newton_step(carry, _):
            A, b, loss = carry                    # (cb,d,C) (cb,C) (cb,)
            P = jax.nn.softmax(xs_dot(A, b).astype(f32), axis=-1).astype(cdt)
            R = Wt_c * (P - Y_c[:, None, :])
            gA, rsum = xs_t_dot(R)
            gA = gA / cnt3 + l2_3 * A
            gb = zero_mean(rsum / cnt[:, None])
            pg = jnp.where(A != 0, gA + l1_3 * jnp.sign(A),
                           jnp.sign(gA) * jnp.maximum(jnp.abs(gA) - l1_3,
                                                      0.0))
            free = ((A != 0) | (l1_3 <= 0)).astype(f32)
            # the Hessian's diagonal: Σ s·xs² = (SᵀXg² − 2 mean·SᵀXg
            # + Σs·mean²) / var, s = w·p(1−p)
            S = Wt_c * (P * (1 - P))
            ssum = jnp.sum(S, axis=0, dtype=f32)            # (cb, C)
            DA = (xt_dot(S, Xg2_c) - 2 * m3 * xt_dot(S, Xg_c)
                  + ssum[:, None, :] * m3 ** 2) / var[:, :, None]
            DA = jnp.maximum(DA, 0.0) / cnt3 + l2_3 + 1e-8
            Db = jnp.maximum(ssum / cnt[:, None], 1e-12)

            def hv(VA, vb):                                 # H·[v; v_b]
                U = xs_dot(VA, vb).astype(f32)
                Pf = P.astype(f32)
                T = Wt_c * (Pf * (U - (Pf * U).sum(axis=-1, keepdims=True))
                            ).astype(cdt)
                hA, tsum = xs_t_dot(T)
                hA = (hA / cnt3 + jnp.maximum(l2_3, 1e-4) * VA) * free
                return hA, zero_mean(tsum / cnt[:, None])

            def dots(uA, ub, vA, vb):
                return (uA * vA).sum(axis=(1, 2)) + (ub * vb).sum(axis=1)

            def cg_step(c, _):
                dA, db, rA, rb, pA, pb, rz = c
                hA, hb = hv(pA, pb)
                alpha = rz / jnp.maximum(dots(pA, pb, hA, hb), 1e-30)
                a3 = alpha[:, None, None]
                dA = dA + a3 * pA
                db = db + alpha[:, None] * pb
                rA = rA - a3 * hA
                rb = rb - alpha[:, None] * hb
                zA, zb = rA / DA, zero_mean(rb / Db)
                rz_new = dots(rA, rb, zA, zb)
                beta = rz_new / jnp.maximum(rz, 1e-30)
                pA = zA + beta[:, None, None] * pA
                pb = zb + beta[:, None] * pb
                return (dA, db, rA, rb, pA, pb, rz_new), None

            rA = pg * free
            zA, zb = rA / DA, zero_mean(gb / Db)
            (dA, db, *_), _ = jax.lax.scan(
                cg_step, (jnp.zeros_like(A), jnp.zeros_like(b), rA, gb,
                          zA, zb, dots(rA, gb, zA, zb)), None,
                length=cg_iters)
            dA = dA + (1.0 - free) * pg / DA

            def try_step(c, t):
                A_k, b_k, loss_k, done = c
                A_t = A - t * dA
                # a coordinate off zero may not change sign in one step
                A_t = jnp.where((l1_3 > 0) & (A_t * A < 0), 0.0, A_t)
                b_t = b - t * db
                loss_t = objective(A_t, b_t)
                take = ~done & (loss_t <= loss + slack * jnp.abs(loss))
                return (jnp.where(take[:, None, None], A_t, A_k),
                        jnp.where(take[:, None], b_t, b_k),
                        jnp.where(take, loss_t, loss_k), done | take), None

            (A, b, loss, _), _ = jax.lax.scan(
                try_step, (A, b, loss, jnp.zeros((cb,), bool)),
                jnp.asarray(_SOFTMAX_STEPS, f32))
            return (A, b, loss), None

        prior = jnp.maximum(
            jnp.dot(W_c, Y_c.astype(f32), preferred_element_type=f32,
                    precision=prec), 0.5) / cnt[:, None]
        A0 = jnp.zeros((cb, d, C), f32)
        b0 = zero_mean(jnp.log(prior))
        with jax.named_scope("linear.softmax_newton_cg"):
            (A, b, _), _ = jax.lax.scan(
                newton_step, (A0, b0, objective(A0, b0)), None,
                length=newton_iters)
        # per-lane standardized -> Xg space -> original space (per class)
        W_g = A * inv
        b_g = b - (W_g * m3).sum(axis=1)
        Wx = W_g / std.g_scale[None, :, None]
        bx = b_g - (Wx * std.g_mean[None, :, None]).sum(axis=1)
        return Wx, zero_mean(bx)

    cb = lane_chunk or softmax_lane_chunk(n, nB, C)
    n_chunks = -(-nB // cb)
    lanes = (W_rows, std.mean, std.scale, std.var, std.cnt,
             reg * elastic_net, reg * (1.0 - elastic_net))
    if n_chunks == 1:
        return one_chunk(lanes)
    idx = jnp.arange(n_chunks * cb) % nB
    lanes = jax.tree_util.tree_map(
        lambda a: a[idx].reshape((n_chunks, cb) + a.shape[1:]), lanes)
    Wx, bx = jax.lax.map(one_chunk, lanes)
    return (Wx.reshape((n_chunks * cb, d, C))[:nB],
            bx.reshape((n_chunks * cb, C))[:nB])


# ---------------------------------------------------------------------------
# Linear / ridge / elastic-net regression: weighted least squares from
# moments (the shape of Spark's WeightedLeastSquares)
#
# A least-squares fit needs its rows once: every lane's weighted second
# moments of [x, 1, y] come from row-block passes over the ONE shared matrix
# (no lane holds a copy of a row), standardisation is algebra on them, and
# every point of the grid is then solved on its lane's (d, d) system. Lanes
# appear only as the (lanes, rows-of-a-block) weights of one contraction.
# ---------------------------------------------------------------------------

#: elements of one row block's (rows, width^2) outer-product temporary in a
#: moment pass: 128 MiB of float32, whatever the lanes and the row count
_GRAM_BLOCK_ELEMS = 2 ** 25

#: proximal-gradient steps of one solve of an elastic-net point on its
#: (d, d) system, power-iteration steps for the Lipschitz constant of its
#: gradient, and refinement passes (each solves every point once more). From
#: this sandbox's CPU against the float64 optimum on 200 000 rows of the
#: taxi table, whose one-hot groups make the Gram matrix singular so that
#: the ridge part (regParam / 2) alone bounds the curvature from below
#: (PR 30, standardised coefficients, regParam 0.001 / 0.01 / 0.1 at
#: elasticNetParam 0.5): 30 steps 4e-1 / 1e-1 / 2e-4, 100 steps 1e-1 /
#: 2e-4 / 4e-7, 300 steps 2e-4 / 2e-5 / 3e-6 and no better at 1 000, 3 000
#: or 10 000 (float32's floor: 1e-4 to 1e-3 at the smallest penalty). Twice
#: the 300 is the schedule. Without a refinement pass the ridge point
#: (0.001, 0) reads 2e-3, with one 2e-4, with two 3e-5, with three 2e-4
_ENET_STEPS = 600
_POWER_STEPS = 64
_REFINE_PASSES = 2


def gram_block_rows(n: int, width: int) -> int:
    """Rows of one block of a moment pass over ``n`` rows whose augmented
    row is ``width`` wide: the largest power of two whose (rows, width^2)
    outer-product temporary stays under ``_GRAM_BLOCK_ELEMS`` (at least 8),
    or all ``n`` rows where they fit in one."""
    r = max(_GRAM_BLOCK_ELEMS // (width * width), 8)
    return min(1 << (r.bit_length() - 1), n)


def for_row_blocks(n: int, rows: int, body, init):
    """``body(carry, start, live)`` folded over the blocks of ``rows`` rows
    that cover ``0..n``: ``start`` is where the block begins and ``live``
    (rows,) marks the rows it owns. The last block is moved back to end at
    ``n`` (its rows that an earlier block owned are not live), so nothing is
    padded or copied."""
    def step(i, carry):
        start = jnp.minimum(i * rows, n - rows)
        live = start + jnp.arange(rows) >= i * rows
        return body(carry, start, live)
    return jax.lax.fori_loop(0, -(-n // rows), step, init)


def global_affine(X):
    """(mean, scale) of every column over all rows: the one affine map the
    moment passes read the shared matrix through, so that float32 sums of
    squares do not cancel (a longitude of -73.97 +- 0.04). Lanes
    standardise on their own rows by algebra on the moments of the mapped
    columns; a column that is constant maps to 0."""
    return X.mean(axis=0), jnp.sqrt(jnp.maximum(X.var(axis=0), 1e-12))


def two_lanes(*lane_arrays):
    """A single lane, fitted twice side by side. With a lane axis of one the
    moment contractions take the backend's matrix-vector path, whose float32
    accumulation over a block's rows is sequential on the CPU (5e-4 of a
    second moment at 200 000 rows, against 3e-6 from the blocked
    matrix-matrix path)."""
    return tuple(jnp.concatenate([a, a]) for a in lane_arrays)


def _lane_moments(X, y, W, g_mean, g_scale, y_mean):
    """(B, d+2, d+2) weighted second moments ``sum_rows w z z'`` of
    ``z = [xg, 1, y - y_mean]`` for every lane's weights ``W`` (B, n), in
    one pass over the rows: float32 accumulation at HIGHEST, the lanes as
    one operand of the contraction."""
    n, d = X.shape
    D = d + 2
    rows = gram_block_rows(n, D)
    slice_rows = partial(jax.lax.dynamic_slice_in_dim, slice_size=rows)

    def body(M, start, live):
        xb = (slice_rows(X, start) - g_mean) / g_scale
        z = jnp.concatenate(
            [xb, jnp.ones((rows, 1), X.dtype),
             (slice_rows(y, start) - y_mean)[:, None]], axis=1)
        outer = (z[:, :, None] * z[:, None, :]).reshape(rows, D * D)
        wb = slice_rows(W, start, axis=1) * live
        return M + jnp.dot(wb, outer, precision=_PREC)

    M = for_row_blocks(n, rows, body,
                       jnp.zeros((W.shape[0], D * D), X.dtype))
    return M.reshape(W.shape[0], D, D)


def _moment_std(M):
    """Per-lane standardisation from the moments of ``_lane_moments``:
    ``(cnt (B,), mean (B, d), scale (B, d), y_bar (B,))``. A column that is
    constant within a lane's weighted rows gets a huge scale (coefficient
    pinned at 0, Spark's zero-variance rule) by ``_BatchStd``'s test:
    relative to its second moment, because one-pass cancellation noise is
    eps times that."""
    d = M.shape[1] - 2
    cnt = jnp.maximum(M[:, d, d], 1.0)
    mean = M[:, :d, d] / cnt[:, None]
    ex2 = jnp.diagonal(M[:, :d, :d], axis1=1, axis2=2) / cnt[:, None]
    var_raw = ex2 - mean ** 2
    dead = var_raw < jnp.maximum(1e-6 * ex2, 1e-10)
    scale = jnp.where(dead, 1e30, jnp.sqrt(jnp.maximum(var_raw, 1e-12)))
    return cnt, mean, scale, M[:, d + 1, d] / cnt


def _residual_moments(X, y, W, g_mean, g_scale, y_mean, coef_g, bias_g):
    """(B, d+1) ``sum_rows w r [xg, 1]`` of the residuals
    ``r = y - y_mean - xg'coef_g - bias_g`` of every lane's fit, in one
    pass over the rows. Sums of residuals do not carry the label's and the
    coefficients' size as the second moments do, so their float32 rounding
    is that much smaller: the fit is corrected with them."""
    n, d = X.shape
    rows = gram_block_rows(n, d + 2)
    slice_rows = partial(jax.lax.dynamic_slice_in_dim, slice_size=rows)

    def body(S, start, live):
        xb = (slice_rows(X, start) - g_mean) / g_scale
        r = ((slice_rows(y, start) - y_mean)[None, :] - bias_g[:, None]
             - jnp.dot(coef_g, xb.T, precision=_PREC))            # (B, rows)
        wr = slice_rows(W, start, axis=1) * live * r
        xa = jnp.concatenate([xb, jnp.ones((rows, 1), X.dtype)], axis=1)
        return S + jnp.dot(wr, xa, precision=_PREC)

    return for_row_blocks(n, rows, body,
                          jnp.zeros((W.shape[0], d + 1), X.dtype))


def _largest_eigenvalue(C):
    """(B,) largest eigenvalue of every lane's positive semi-definite C
    (B, d, d) by ``_POWER_STEPS`` power-iteration steps, with a twentieth
    of room: the Lipschitz constant of the quadratic's gradient."""
    d = C.shape[-1]

    def power(v, _):
        v = jnp.einsum("bij,bj->bi", C, v, precision=_PREC)
        return v / jnp.maximum(jnp.linalg.norm(v, axis=1, keepdims=True),
                               1e-30), None

    v0 = jnp.broadcast_to(1.0 + jnp.arange(d, dtype=C.dtype) / d,
                          C.shape[:2])
    v, _ = jax.lax.scan(power, v0 / jnp.linalg.norm(v0[0]), None,
                        length=_POWER_STEPS)
    return 1.05 * (v * jnp.einsum("bij,bj->bi", C, v,
                                  precision=_PREC)).sum(axis=1)


def _solve_points(C, c, l1, l2, top, a0=None):
    """Every lane's minimiser of ``a'Ca/2 - c'a + l2/2 |a|^2 + l1 |a|_1``
    (C (B, d, d) positive semi-definite with largest eigenvalue ``top``,
    the rest (B, d) or (B,)). Without an L1 term it is the (d, d) solve.
    With one: ``_ENET_STEPS`` accelerated proximal-gradient steps from
    ``a0`` (else from that solve) at the step 1 / (top + l2)."""
    d = C.shape[-1]
    ridge = jnp.linalg.solve(
        C + (l2 + 1e-8)[:, None, None] * jnp.eye(d, dtype=C.dtype),
        c[:, :, None])[:, :, 0]
    mv = lambda v: jnp.einsum("bij,bj->bi", C, v, precision=_PREC)
    lips = top + l2 + 1e-8
    step = (1.0 / lips)[:, None]
    thresh = step * l1[:, None]

    # the ridge part makes the objective strongly convex (modulus l2), so
    # the momentum is the constant that gives the rate 1 - sqrt(l2 / L) a
    # step and needs no restart test (in float32 a restart test fires on
    # rounding and stalls the weak directions); a pure L1 point (l2 = 0)
    # runs at the momentum of modulus L / 10 000
    q = jnp.sqrt(jnp.maximum(l2 / lips, 1e-4))
    mom = ((1.0 - q) / (1.0 + q))[:, None]

    def fista(carry, _):
        a, u = carry
        w = u - step * (mv(u) + l2[:, None] * u - c)
        a_new = jnp.sign(w) * jnp.maximum(jnp.abs(w) - thresh, 0.0)
        return (a_new, a_new + mom * (a_new - a)), None

    start = ridge if a0 is None else a0
    (a, _), _ = jax.lax.scan(fista, (start, start), None,
                             length=_ENET_STEPS)
    return jnp.where(l1[:, None] > 0, a, ridge)


@jax.jit
def _fit_linreg_batch(X, y, W, reg, elastic_net):
    """Fit B linear regressions at once. W: (B, n) per-lane row weights;
    reg / elastic_net: (B,). Returns (coef (B, d), bias (B,)) in the
    features' own scale.

    Objective (Spark ML's, features standardised on the lane's weighted
    rows, label as it is, intercept free and unpenalised):
    ``mean_w (y - x_s'a - b)^2 / 2 + reg (alpha |a|_1 + (1 - alpha)/2
    |a|^2)``. Two passes over the rows for the one global affine map, ONE
    for every lane's moments; a ridge point is then a (d, d) solve, a point
    with an L1 term runs proximal gradient on the same system to its
    optimum. ``_REFINE_PASSES`` more passes take the fits' residual moments
    and every point is solved again with them in the cross moments' place
    (iterative refinement: what float32 lost in the second moments, along
    the directions that one-hot groups leave to the penalty alone, comes
    back)."""
    if W.shape[0] == 1:
        coef, bias = _fit_linreg_batch(X, y, *two_lanes(W, reg, elastic_net))
        return coef[:1], bias[:1]
    g_mean, g_scale = global_affine(X)
    y_mean = y.mean()
    M = _lane_moments(X, y, W, g_mean, g_scale, y_mean)
    d = X.shape[1]
    cnt, mean, scale, y_bar = _moment_std(M)
    C = ((M[:, :d, :d] / cnt[:, None, None]
          - mean[:, :, None] * mean[:, None, :])
         / (scale[:, :, None] * scale[:, None, :]))
    c = (M[:, :d, d + 1] / cnt[:, None] - mean * y_bar[:, None]) / scale
    l2 = reg * (1.0 - elastic_net)
    l1 = reg * elastic_net
    top = _largest_eigenvalue(C)
    a = _solve_points(C, c, l1, l2, top)
    r_bar = jnp.zeros_like(y_bar)
    for _ in range(_REFINE_PASSES):
        bias_g = y_bar + r_bar - (a / scale * mean).sum(axis=1)
        S = _residual_moments(X, y, W, g_mean, g_scale, y_mean, a / scale,
                              bias_g)
        r_bar = r_bar + S[:, d] / cnt
        c_fix = (jnp.einsum("bij,bj->bi", C, a, precision=_PREC)
                 + (S[:, :d] - mean * S[:, d:]) / (cnt[:, None] * scale))
        a = _solve_points(C, c_fix, l1, l2, top, a)
    coef_g = a / scale
    coef = coef_g / g_scale
    bias = (y_bar + r_bar + y_mean - (coef_g * mean).sum(axis=1)
            - (coef * g_mean).sum(axis=1))
    return coef, bias


def _fit_linreg(X, y, w, reg, elastic_net):
    """Single-config fit: the B=1 slice of the batched solver."""
    coef, bias = _fit_linreg_batch(
        X, y, w[None, :], jnp.asarray([reg], X.dtype),
        jnp.asarray([elastic_net], X.dtype))
    return coef[0], bias[0]


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

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        # the global affine map reads the rows twice (mean, deviation), the
        # moments once, the residual moments once; the small solver runs,
        # before and after the correction, only where a lane has an L1 term
        l1 = any(g.get("regParam", 0) * g.get("elasticNetParam", 0) > 0
                 for g in grid)
        return {"gramPasses": 3 + _REFINE_PASSES,
                "solveSteps": (1 + _REFINE_PASSES) * _ENET_STEPS if l1 else 0}

    def predict_batch(self, params, X, num_classes):
        return jnp.einsum("bd,nd->bn", params["coef"], X, precision=_PREC) \
            + params["bias"][:, None]

    def predict_parts(self, fitted: FittedParams, X):
        # a real (n,d)@(d,) product: at the chip's default precision its
        # bfloat16 pass moves a fare by cents (PR 26 found the same for the
        # multiclass probabilities)
        pred = jnp.dot(X, jnp.asarray(fitted.params["coef"]),
                       precision=_PREC) + fitted.params["bias"]
        return {"prediction": pred}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


# ---------------------------------------------------------------------------
# Linear SVC — squared hinge + L2, Nesterov accelerated GD, batched over
# configs via the same shared-matmul standardization algebra as logistic.
# ---------------------------------------------------------------------------

#: gradient steps of the SVC fit, a sweep candidate's and the refit's alike
_SVC_STEPS = 100


def svc_matrix_passes(sweep: bool) -> int:
    """Reads of the (rows, features) matrix in the compiled
    `_fit_svc_batch` (as :func:`logreg_matrix_passes`): five to standardise
    and cast for a sweep's bfloat16 lanes, three for the refit's float32
    lane, then a step's margin and gradient products."""
    return (5 if sweep else 3) + 2 * _SVC_STEPS


@partial(jax.jit, static_argnames=("iters", "sweep"))
def _fit_svc_batch(X, y, W, reg, iters=_SVC_STEPS, sweep=False):
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


def _fit_svc(X, y, w, reg, iters=_SVC_STEPS):
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

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        return {"matrixPasses": svc_matrix_passes(sweep)}

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
