"""Generalized linear model family.

TPU-native replacement for the reference's Spark GLR wrapper (reference:
core/.../impl/regression/OpGeneralizedLinearRegression.scala; default grid
DistFamily {gaussian, poisson} × Regularization per DefaultSelectorParams).

One IRLS (iteratively reweighted least squares) loop of fixed length fits
every distribution family of every lane at once over the ONE shared feature
matrix: the working response and weights are selected by a traced family
code, so a mixed gaussian/poisson grid is one XLA program; lanes appear only
in (rows-of-a-block, lanes) vectors (eta, mu, working weights and response)
and in the (lanes, d+1, d+1) weighted Gram matrices that each pass over the
rows accumulates by blocks (``linear.for_row_blocks``). No lane holds a copy
of a row.

Links: gaussian → identity; poisson / gamma / tweedie → log (Spark's gamma
default link is inverse; log is used here for numerical robustness on
standardized features — documented deviation).

The penalty is ``regParam / 2 * |coef|^2`` on the coefficients of the
features AS THEY ARE, not standardised (Spark's rule for its IRLS path:
``IterativelyReweightedLeastSquares`` fits each step with
``standardizeFeatures = false``; its special case of a gaussian family with
the identity link, which goes through a standardised ``WeightedLeastSquares``
instead, is not copied: a gaussian point here is the same objective as a
poisson one, squared error in the deviance's place). The rows are read
through one global affine map (``linear.global_affine``) so that float32
Gram sums do not cancel; the penalty is carried into that space exactly
(``regParam / scale^2`` a column) and the intercept is free, so the map
changes no result.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .api import FittedParams, ModelFamily, register_family
from .linear import (for_row_blocks, global_affine, gram_block_rows,
                     two_lanes)

_PREC = jax.lax.Precision.HIGHEST

#: distribution family codes (carried as float32 through grid arrays)
FAMILY_CODES = {"gaussian": 0.0, "poisson": 1.0, "gamma": 2.0, "tweedie": 3.0}

#: IRLS steps of one fit. From the family's initial mean a log-link lane of
#: the stock grid is at its float32 floor after 6 to 8 steps on this
#: sandbox's CPU (PR 30, CHANGES.md); the rest is room for a label the link
#: fits badly, which the best-iterate guard then decides
_IRLS_ITERS = 8

#: a log-link family's initial mean is the label kept off zero by this much
#: (Spark's ``Poisson.initialize``, R's ``poisson()$initialize``)
_MU_FLOOR = 0.1


@partial(jax.jit, static_argnames=("iters",))
def _fit_glm_batch(X, y, W, reg, fam, var_power, iters=_IRLS_ITERS):
    """IRLS for B lanes at once. W: (B, n) row weights; reg, fam (family
    code), var_power (tweedie variance power, Var(μ) = μ^p; ignored for the
    other families): (B,). Returns (coef (B, d), bias (B,)).

    IRLS starts from the family's initial mean (``FamilyAndLink.initialize``
    in Spark): gaussian ``mu0 = y``, log-link families ``mu0 = max(y, 0.1)``
    and ``eta0 = log mu0``, so the first working response is the label's own
    logarithm and not ``y - 1``. Each of the ``iters + 1`` passes over the
    rows gives every lane the deviance of its current coefficients (for the
    best-iterate guard) and the weighted Gram system of its next ones."""
    if W.shape[0] == 1:
        coef, bias = _fit_glm_batch(X, y, *two_lanes(W, reg, fam, var_power),
                                    iters=iters)
        return coef[:1], bias[:1]
    n, d = X.shape
    B = W.shape[0]
    D = d + 1
    g_mean, g_scale = global_affine(X)
    rows = gram_block_rows(n, D)
    slice_rows = partial(jax.lax.dynamic_slice_in_dim, slice_size=rows)
    cnt = jnp.maximum(W.sum(axis=1), 1.0)
    is_gauss = (fam == FAMILY_CODES["gaussian"])[None, :]
    # variance power: gaussian 0 (unused), poisson 1, gamma 2, tweedie p
    p = jnp.where(fam == FAMILY_CODES["poisson"], 1.0,
                  jnp.where(fam == FAMILY_CODES["gamma"], 2.0, var_power))
    ridge = jnp.concatenate(
        [reg[:, None] / g_scale[None, :] ** 2, jnp.zeros((B, 1), X.dtype)],
        axis=1) + 1e-8
    eye = jnp.eye(D, dtype=X.dtype)

    def one_pass(theta, first):
        """(deviance (B,), Gram (B, D, D), right-hand side (B, D)) at
        ``theta`` (B, D); ``first``: at the family's initial mean."""
        def body(carry, start, live):
            A, rhs, loss = carry
            xb = (slice_rows(X, start) - g_mean) / g_scale
            yb = slice_rows(y, start)[:, None]
            wb = (slice_rows(W, start, axis=1) * live).T          # (rows, B)
            eta = jnp.dot(xb, theta[:, :d].T, precision=_PREC) + theta[:, d]
            eta = jnp.where(first, jnp.where(
                is_gauss, yb, jnp.log(jnp.maximum(yb, _MU_FLOOR))), eta)
            eta_c = jnp.clip(eta, -30.0, 30.0)
            mu = jnp.maximum(jnp.exp(eta_c), 1e-12)
            # log link: W = μ^(2-p), z = η + (y-μ)/μ ; identity: W = 1, z = y
            wk = jnp.where(is_gauss, 1.0, jnp.power(mu, 2.0 - p)) * wb
            z = jnp.where(is_gauss, yb,
                          jnp.clip(eta + (yb - mu) / mu, -1e6, 1e6))
            # the step is solved for the CHANGE of the coefficients (the
            # working response less the margin they already give), so that
            # what float32 loses in one pass's Gram sums the next corrects
            z = jnp.where(first, z, z - eta)
            # weighted deviance-like loss per family: gaussian squared
            # error; log link -y·η + μ (poisson-shaped surrogate, monotone
            # in fit quality for the log-link families)
            dev = jnp.where(is_gauss, 0.5 * (yb - eta) ** 2, mu - yb * eta_c)
            xa = jnp.concatenate([xb, jnp.ones((rows, 1), X.dtype)], axis=1)
            outer = (xa[:, :, None] * xa[:, None, :]).reshape(rows, D * D)
            return (A + jnp.dot(wk.T, outer, precision=_PREC),
                    rhs + jnp.dot((wk * z).T, xa, precision=_PREC),
                    loss + (dev * wb).sum(axis=0))

        A, rhs, loss = for_row_blocks(
            n, rows, body, (jnp.zeros((B, D * D), X.dtype),
                            jnp.zeros((B, D), X.dtype),
                            jnp.zeros((B,), X.dtype)))
        return (loss / cnt, A.reshape(B, D, D) / cnt[:, None, None],
                rhs / cnt[:, None])

    def step(carry, k):
        theta, best_theta, best_loss = carry
        first = k == 0
        loss, A, rhs = one_pass(theta, first)
        # divergence guard: track the best iterate (mismatched family/link
        # configs — e.g. log link on negative targets — oscillate or blow
        # up; keep the best-deviance parameters instead of the last)
        # (a later iterate whose deviance ties within float32's rounding
        # of the sum is the more refined one, and is taken)
        better = ~first & (loss <= best_loss + 1e-6 * jnp.abs(best_loss))
        best_theta = jnp.where(better[:, None], theta, best_theta)
        best_loss = jnp.where(better, jnp.minimum(loss, best_loss), best_loss)
        prop = theta + jnp.linalg.solve(
            A + ridge[:, :, None] * eye,
            (rhs - ridge * theta)[:, :, None])[:, :, 0]
        ok = jnp.all(jnp.isfinite(prop), axis=1, keepdims=True)
        return (jnp.where(ok, prop, theta), best_theta, best_loss), None

    theta0 = jnp.zeros((B, D), X.dtype)
    (_, theta, _), _ = jax.lax.scan(
        step, (theta0, theta0, jnp.full((B,), jnp.inf, X.dtype)),
        jnp.arange(iters + 1))
    coef = theta[:, :d] / g_scale
    return coef, theta[:, d] - (coef * g_mean).sum(axis=1)


def _glm_mean(margin, fam):
    mu_log = jnp.exp(jnp.clip(margin, -30.0, 30.0))
    return jnp.where(fam == FAMILY_CODES["gaussian"], margin, mu_log)


class GeneralizedLinearRegressionFamily(ModelFamily):
    """reference OpGeneralizedLinearRegression (defaults: family
    {gaussian, poisson}, regParam per DefaultSelectorParams.Regularization)."""

    name = "OpGeneralizedLinearRegression"
    supports = frozenset({"regression"})

    def default_grid(self, problem: str) -> List[Dict[str, Any]]:
        return [{"family": f, "regParam": r}
                for f in ("gaussian", "poisson")
                for r in (0.001, 0.01, 0.1, 0.2)]

    def grid_to_arrays(self, grid: Sequence[Dict[str, Any]]) -> Dict[str, jnp.ndarray]:
        coded = []
        for g in grid:
            g = dict(g)
            famval = g.get("family", "gaussian")
            if isinstance(famval, str):
                g["family"] = FAMILY_CODES[famval]
            g.setdefault("variancePower", 1.5)
            coded.append(g)
        return super().grid_to_arrays(coded)

    def fit_batch(self, X, y, weights, grid, num_classes):
        fam = grid.get("family")
        if fam is None:
            fam = jnp.zeros_like(grid["regParam"])
        vp = grid.get("variancePower")
        if vp is None:
            vp = jnp.full_like(fam, 1.5)
        coef, bias = _fit_glm_batch(X, y, weights, grid["regParam"], fam, vp)
        return {"coef": coef, "bias": bias, "family": fam}

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        # two passes for the global affine map, then one a step and one more
        # for the last step's deviance
        return {"irlsIters": _IRLS_ITERS, "gramPasses": _IRLS_ITERS + 3}

    def predict_batch(self, params, X, num_classes):
        margin = jnp.einsum("bd,nd->bn", params["coef"], X, precision=_PREC) \
            + params["bias"][:, None]
        return _glm_mean(margin, params["family"][:, None])

    def predict_parts(self, fitted: FittedParams, X):
        margin = jnp.dot(X, jnp.asarray(fitted.params["coef"]),
                         precision=_PREC) + fitted.params["bias"]
        pred = _glm_mean(margin, jnp.asarray(fitted.params["family"]))
        return {"prediction": pred}

    def predict_one(self, fitted: FittedParams, X) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, X).items()}


register_family(GeneralizedLinearRegressionFamily())
