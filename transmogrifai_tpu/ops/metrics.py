"""Jitted evaluation-metric kernels.

TPU replacements for Spark MLlib's BinaryClassificationMetrics /
MulticlassMetrics / RegressionMetrics used by the reference evaluators
(reference: core/.../evaluators/OpBinaryClassificationEvaluator.scala:68,
OpMultiClassificationEvaluator.scala, OpRegressionEvaluator.scala): sort-based
scans on device instead of RDD aggregations.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .stats import _rank

# -- binned threshold curves (large-n path) ----------------------------------
# Above this row count, AuROC/AuPR switch from exact sort-based scans to
# binned threshold curves — the same downsampling Spark's
# BinaryClassificationMetrics applies (numBins=1000 there; 4096 here), but
# computed sort- and scatter-free: bin indices split into a (64, 64)
# high/low pair and the histogram becomes chunked one-hot outer-product
# matmuls that tile onto the MXU.
_BINNED_MIN_N = 100_000
_NUM_BINS = 4096
_HI = 64
_LO = _NUM_BINS // _HI
_HIST_CHUNK = 32768


@jax.named_scope("metrics.binned")
def _binned_hists(scores: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray):
    """(pos_hist, total_hist), each (_NUM_BINS,), over the masked subset;
    bins span the masked score range (descending-threshold curves read the
    histograms reversed)."""
    n = scores.shape[0]
    inf = jnp.asarray(jnp.inf, scores.dtype)
    smin = jnp.min(jnp.where(mask, scores, inf))
    smax = jnp.max(jnp.where(mask, scores, -inf))
    width = jnp.maximum(smax - smin, 1e-12)
    idx = jnp.clip(((scores - smin) / width * _NUM_BINS).astype(jnp.int32),
                   0, _NUM_BINS - 1)
    w = mask.astype(scores.dtype)
    pos = w * (labels > 0.5)
    pad = (-n) % _HIST_CHUNK
    if pad:
        idx = jnp.pad(idx, (0, pad))      # padded rows carry zero weight
        w = jnp.pad(w, (0, pad))
        pos = jnp.pad(pos, (0, pad))
    hi = idx // _LO
    lo = idx % _LO
    iot_hi = jnp.arange(_HI, dtype=jnp.int32)
    iot_lo = jnp.arange(_LO, dtype=jnp.int32)

    def step(carry, k):
        hp, ha = carry
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, k * _HIST_CHUNK,
                                                    _HIST_CHUNK)
        h, l = sl(hi), sl(lo)
        # 0/1 weights: int8 operands with int32 accumulation are exact and
        # run the MXU at twice the bf16 rate on v5e
        wp = sl(pos).astype(jnp.int8)
        wa = sl(w).astype(jnp.int8)
        oh_hi = (h[:, None] == iot_hi).astype(jnp.int8)
        oh_lo = (l[:, None] == iot_lo).astype(jnp.int8)
        hp = hp + jnp.einsum("nh,nl->hl", oh_hi * wp[:, None], oh_lo,
                             preferred_element_type=jnp.int32)
        ha = ha + jnp.einsum("nh,nl->hl", oh_hi * wa[:, None], oh_lo,
                             preferred_element_type=jnp.int32)
        return (hp, ha), None

    z = jnp.zeros((_HI, _LO), jnp.int32)
    (hp, ha), _ = jax.lax.scan(step, (z, z),
                               jnp.arange((n + pad) // _HIST_CHUNK))
    return (hp.reshape(-1).astype(jnp.float32),
            ha.reshape(-1).astype(jnp.float32))


def _auroc_from_hists(hp: jnp.ndarray, ha: jnp.ndarray) -> jnp.ndarray:
    """Trapezoid over the binned ROC curve: each bin is one tie group, so this
    is the grouped tie-corrected Mann-Whitney statistic."""
    hp, ha = hp[::-1], ha[::-1]
    hn = ha - hp
    ctp, cfp = jnp.cumsum(hp), jnp.cumsum(hn)
    n_pos, n_neg = ctp[-1], cfp[-1]
    tpr = ctp / jnp.maximum(n_pos, 1.0)
    fpr = cfp / jnp.maximum(n_neg, 1.0)
    tp = jnp.concatenate([jnp.zeros(1, tpr.dtype), tpr[:-1]])
    fp = jnp.concatenate([jnp.zeros(1, fpr.dtype), fpr[:-1]])
    area = ((fpr - fp) * (tpr + tp) / 2).sum()
    return jnp.where((n_pos > 0) & (n_neg > 0), area, 0.0)


def _aupr_from_hists(hp: jnp.ndarray, ha: jnp.ndarray) -> jnp.ndarray:
    """Binned precision-recall curve, first point at (recall 0, precision 1)
    matching the exact path's convention."""
    hp, ha = hp[::-1], ha[::-1]
    hn = ha - hp
    ctp, cfp = jnp.cumsum(hp), jnp.cumsum(hn)
    n_pos = jnp.maximum(ctp[-1], 1.0)
    rec = ctp / n_pos
    prec = ctp / jnp.maximum(ctp + cfp, 1.0)
    rp = jnp.concatenate([jnp.zeros(1, rec.dtype), rec[:-1]])
    pp = jnp.concatenate([jnp.ones(1, prec.dtype), prec[:-1]])
    return ((rec - rp) * (prec + pp) / 2).sum()


@jax.jit
def binary_confusion(scores: jnp.ndarray, labels: jnp.ndarray,
                     threshold: float = 0.5):
    """(tp, tn, fp, fn) at a score threshold."""
    pred = (scores >= threshold).astype(jnp.float32)
    pos = (labels > 0.5).astype(jnp.float32)
    tp = (pred * pos).sum()
    fp = (pred * (1 - pos)).sum()
    fn = ((1 - pred) * pos).sum()
    tn = ((1 - pred) * (1 - pos)).sum()
    return tp, tn, fp, fn


@jax.jit
def auroc(scores: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """AuROC: exact Mann-Whitney rank formula (tie-correct); above
    _BINNED_MIN_N rows, binned threshold curves (Spark-style downsampling)."""
    if scores.shape[0] >= _BINNED_MIN_N:
        return _auroc_from_hists(
            *_binned_hists(scores, labels, jnp.ones_like(scores, jnp.bool_)))
    pos = (labels > 0.5).astype(scores.dtype)
    n_pos = pos.sum()
    n_neg = pos.shape[0] - n_pos
    ranks = _rank(scores)
    pos_rank_sum = (ranks * pos).sum()
    u = pos_rank_sum - n_pos * (n_pos + 1) / 2.0
    return jnp.where((n_pos > 0) & (n_neg > 0), u / jnp.maximum(n_pos * n_neg, 1.0), 0.0)


@jax.jit
def aupr(scores: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Area under the precision-recall curve, linear interpolation over
    distinct-threshold boundary points (matches Spark's areaUnderPR up to its
    first-point convention); binned above _BINNED_MIN_N rows."""
    if scores.shape[0] >= _BINNED_MIN_N:
        return _aupr_from_hists(
            *_binned_hists(scores, labels, jnp.ones_like(scores, jnp.bool_)))
    n = scores.shape[0]
    order = jnp.argsort(-scores)
    s = scores[order]
    y = (labels[order] > 0.5).astype(scores.dtype)
    cum_tp = jnp.cumsum(y)
    cum_fp = jnp.cumsum(1.0 - y)
    n_pos = jnp.maximum(cum_tp[-1], 1.0)
    # points valid only at tie-group boundaries (last index of equal scores)
    boundary = jnp.concatenate([s[1:] != s[:-1], jnp.array([True])])
    recall = cum_tp / n_pos
    precision = cum_tp / jnp.maximum(cum_tp + cum_fp, 1.0)
    # previous boundary's (recall, precision) for each boundary point
    idx = jnp.arange(n)
    b_idx = jnp.where(boundary, idx, -1)
    prev_b = jnp.concatenate([jnp.array([-1]), jax.lax.cummax(b_idx)[:-1]])
    r_prev = jnp.where(prev_b >= 0, recall[jnp.maximum(prev_b, 0)], 0.0)
    p_prev = jnp.where(prev_b >= 0, precision[jnp.maximum(prev_b, 0)], 1.0)
    seg = (recall - r_prev) * (precision + p_prev) / 2.0
    return jnp.where(boundary, seg, 0.0).sum()


@partial(jax.jit, static_argnames=("binned",))
def auroc_masked(scores: jnp.ndarray, labels: jnp.ndarray,
                 mask: jnp.ndarray, binned: Optional[bool] = None
                 ) -> jnp.ndarray:
    """AuROC over the masked subset. Masked rows get +inf scores (ranking above
    all valid rows, so valid ranks 1..n_valid are unchanged) and are excluded
    from the positive/negative counts — used inside vmapped CV where every fold
    shares one static shape. Binned above _BINNED_MIN_N rows; pass ``binned``
    to pin the algorithm regardless of shape (the fold-sliced CV path pins it
    to the pre-slice row count so results match full-row scoring)."""
    use_binned = (binned if binned is not None
                  else scores.shape[0] >= _BINNED_MIN_N)
    if use_binned:
        return _auroc_from_hists(*_binned_hists(scores, labels, mask))
    s = jnp.where(mask, scores, jnp.inf)
    pos = (labels > 0.5) & mask
    n_pos = pos.sum().astype(scores.dtype)
    n_neg = mask.sum().astype(scores.dtype) - n_pos
    ranks = _rank(s)
    pos_rank_sum = (ranks * pos.astype(scores.dtype)).sum()
    u = pos_rank_sum - n_pos * (n_pos + 1) / 2.0
    return jnp.where((n_pos > 0) & (n_neg > 0), u / jnp.maximum(n_pos * n_neg, 1.0), 0.0)


@partial(jax.jit, static_argnames=("binned",))
def aupr_masked(scores: jnp.ndarray, labels: jnp.ndarray,
                mask: jnp.ndarray, binned: Optional[bool] = None
                ) -> jnp.ndarray:
    """AuPR over the masked subset (masked rows sink to -inf and contribute
    nothing to cumulative TP/FP, so curve deltas in their range are zero).
    Binned above _BINNED_MIN_N rows; ``binned`` pins the algorithm (see
    auroc_masked)."""
    use_binned = (binned if binned is not None
                  else scores.shape[0] >= _BINNED_MIN_N)
    if use_binned:
        return _aupr_from_hists(*_binned_hists(scores, labels, mask))
    n = scores.shape[0]
    s_in = jnp.where(mask, scores, -jnp.inf)
    order = jnp.argsort(-s_in)
    s = s_in[order]
    valid = mask[order].astype(scores.dtype)
    y = (labels[order] > 0.5).astype(scores.dtype) * valid
    cum_tp = jnp.cumsum(y)
    cum_fp = jnp.cumsum(valid - y)
    n_pos = jnp.maximum(cum_tp[-1], 1.0)
    boundary = jnp.concatenate([s[1:] != s[:-1], jnp.array([True])])
    recall = cum_tp / n_pos
    precision = cum_tp / jnp.maximum(cum_tp + cum_fp, 1.0)
    idx = jnp.arange(n)
    b_idx = jnp.where(boundary, idx, -1)
    prev_b = jnp.concatenate([jnp.array([-1]), jax.lax.cummax(b_idx)[:-1]])
    r_prev = jnp.where(prev_b >= 0, recall[jnp.maximum(prev_b, 0)], 0.0)
    p_prev = jnp.where(prev_b >= 0, precision[jnp.maximum(prev_b, 0)], 1.0)
    seg = (recall - r_prev) * (precision + p_prev) / 2.0
    return jnp.where(boundary, seg, 0.0).sum()


@jax.jit
def binary_threshold_metrics_masked(scores: jnp.ndarray, labels: jnp.ndarray,
                                    mask: jnp.ndarray, threshold: float = 0.5):
    """Precision/Recall/F1/Error at a probability threshold over the masked
    subset (vmapped-CV fast path; assumes probability-like scores)."""
    w = mask.astype(scores.dtype)
    pred = (scores >= threshold).astype(scores.dtype) * w
    pos = (labels > 0.5).astype(scores.dtype) * w
    tp = (pred * pos).sum()
    fp = (pred * (w - pos)).sum()
    fn = ((w - pred) * pos).sum()
    cnt = jnp.maximum(w.sum(), 1.0)
    prec = tp / jnp.maximum(tp + fp, 1.0)
    rec = tp / jnp.maximum(pos.sum(), 1.0)
    f1 = jnp.where(prec + rec > 0,
                   2 * prec * rec / jnp.maximum(prec + rec, 1e-30), 0.0)
    err = (fp + fn) / cnt
    return {"Precision": prec, "Recall": rec, "F1": f1, "Error": err}


@partial(jax.jit, static_argnames=("num_classes",))
def multiclass_metrics_masked(pred_idx: jnp.ndarray, label_idx: jnp.ndarray,
                              mask: jnp.ndarray, num_classes: int):
    """Weighted Precision/Recall/F1 + Error over the masked subset."""
    w = mask.astype(jnp.float32)
    p = jax.nn.one_hot(pred_idx, num_classes, dtype=jnp.float32) * w[:, None]
    l = jax.nn.one_hot(label_idx, num_classes, dtype=jnp.float32) * w[:, None]
    cm = l.T @ p
    n = jnp.maximum(cm.sum(), 1.0)
    support = cm.sum(axis=1)
    pred_cnt = cm.sum(axis=0)
    tp = jnp.diag(cm)
    prec_c = tp / jnp.maximum(pred_cnt, 1.0)
    rec_c = tp / jnp.maximum(support, 1.0)
    f1_c = jnp.where(prec_c + rec_c > 0,
                     2 * prec_c * rec_c / jnp.maximum(prec_c + rec_c, 1e-30), 0.0)
    wgt = support / n
    return {"Error": 1.0 - jnp.trace(cm) / n,
            "Precision": (prec_c * wgt).sum(),
            "Recall": (rec_c * wgt).sum(),
            "F1": (f1_c * wgt).sum()}


@jax.jit
def regression_metrics_masked(pred: jnp.ndarray, label: jnp.ndarray,
                              mask: jnp.ndarray):
    w = mask.astype(pred.dtype)
    cnt = jnp.maximum(w.sum(), 1.0)
    err = (pred - label) * w
    mse = (err ** 2).sum() / cnt
    label_mean = (label * w).sum() / cnt
    ss_tot = (((label - label_mean) * w) ** 2).sum()
    r2 = jnp.where(ss_tot > 0, 1.0 - (err ** 2).sum() / jnp.maximum(ss_tot, 1e-30), 0.0)
    return {"RootMeanSquaredError": jnp.sqrt(mse), "MeanSquaredError": mse,
            "MeanAbsoluteError": jnp.abs(err).sum() / cnt, "R2": r2}


def log_loss_masked(scores: jnp.ndarray, labels: jnp.ndarray,
                    mask: jnp.ndarray) -> jnp.ndarray:
    """Binary log loss over the masked subset (validation-sweep variant of
    ``log_loss``)."""
    p = jnp.clip(scores, 1e-15, 1 - 1e-15)
    y = (labels > 0.5).astype(scores.dtype)
    w = mask.astype(scores.dtype)
    ll = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p)) * w
    return ll.sum() / jnp.maximum(w.sum(), 1.0)


@partial(jax.jit, static_argnames=("num_bins",))
def threshold_metrics(scores: jnp.ndarray, labels: jnp.ndarray,
                      num_bins: int = 100):
    """Precision/recall/F1 over evenly spaced thresholds (reference
    threshold curves in BinaryClassificationMetrics)."""
    thresholds = jnp.linspace(0.0, 1.0, num_bins)
    pos = (labels > 0.5).astype(scores.dtype)
    n_pos = jnp.maximum(pos.sum(), 1.0)

    def at(t):
        pred = (scores >= t).astype(scores.dtype)
        tp = (pred * pos).sum()
        fp = (pred * (1 - pos)).sum()
        prec = tp / jnp.maximum(tp + fp, 1.0)
        rec = tp / n_pos
        f1 = jnp.where(prec + rec > 0, 2 * prec * rec / jnp.maximum(prec + rec, 1e-30), 0.0)
        return prec, rec, f1

    prec, rec, f1 = jax.vmap(at)(thresholds)
    return thresholds, prec, rec, f1


@partial(jax.jit, static_argnames=("num_classes",))
def multiclass_confusion(pred_idx: jnp.ndarray, label_idx: jnp.ndarray,
                         num_classes: int) -> jnp.ndarray:
    """(C, C) confusion matrix rows=label, cols=pred — one-hot matmul."""
    p = jax.nn.one_hot(pred_idx, num_classes, dtype=jnp.float32)
    l = jax.nn.one_hot(label_idx, num_classes, dtype=jnp.float32)
    return l.T @ p


@partial(jax.jit, static_argnames=("num_classes",))
def multiclass_metrics(pred_idx: jnp.ndarray, label_idx: jnp.ndarray,
                       num_classes: int):
    """error, weighted precision/recall/F1 (reference
    OpMultiClassificationEvaluator default metrics)."""
    cm = multiclass_confusion(pred_idx, label_idx, num_classes)
    n = jnp.maximum(cm.sum(), 1.0)
    correct = jnp.trace(cm)
    support = cm.sum(axis=1)                   # per true class
    pred_cnt = cm.sum(axis=0)
    tp = jnp.diag(cm)
    prec_c = tp / jnp.maximum(pred_cnt, 1.0)
    rec_c = tp / jnp.maximum(support, 1.0)
    f1_c = jnp.where(prec_c + rec_c > 0,
                     2 * prec_c * rec_c / jnp.maximum(prec_c + rec_c, 1e-30), 0.0)
    w = support / n
    return {
        "Error": 1.0 - correct / n,
        "Precision": (prec_c * w).sum(),
        "Recall": (rec_c * w).sum(),
        "F1": (f1_c * w).sum(),
    }


@jax.jit
def regression_metrics(pred: jnp.ndarray, label: jnp.ndarray):
    """RMSE/MSE/MAE/R² (reference OpRegressionEvaluator.scala)."""
    err = pred - label
    mse = (err ** 2).mean()
    mae = jnp.abs(err).mean()
    ss_res = (err ** 2).sum()
    ss_tot = ((label - label.mean()) ** 2).sum()
    r2 = jnp.where(ss_tot > 0, 1.0 - ss_res / jnp.maximum(ss_tot, 1e-30), 0.0)
    return {"RootMeanSquaredError": jnp.sqrt(mse), "MeanSquaredError": mse,
            "MeanAbsoluteError": mae, "R2": r2}


@jax.jit
def log_loss(prob_pos: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Binary log loss (reference impl/evaluator/OPLogLoss.scala)."""
    p = jnp.clip(prob_pos, 1e-15, 1 - 1e-15)
    y = (labels > 0.5).astype(prob_pos.dtype)
    return -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p)).mean()


@partial(jax.jit, static_argnames=("top_ns",))
def multiclass_rank_metrics(probs: jnp.ndarray, label_idx: jnp.ndarray,
                            mask: jnp.ndarray, thresholds: jnp.ndarray,
                            top_ns: tuple):
    """What a multiclass evaluation reads off the (n, C) probabilities, over
    the masked rows, by counting: no sort.

    The true class's rank is ``#{c : p_c > p_true, or p_c == p_true and
    c < true}``: its place in a STABLE descending sort, so tied classes rank
    by index. A row is a top-N hit when that rank is below N; a row whose
    label has no column (below 0, C or above: a class the cutter dropped)
    hits nothing and is left out of the log loss. A prediction is made at
    threshold t when the largest probability is >= t; ``thresholds`` are
    float32 (see ``evaluators.multi``).

    Returns ``LogLoss`` (float32 mean over the rows with a column), ``rows``
    (masked rows), ``hits`` (len(top_ns),), ``made`` (T,) and ``correct``
    (len(top_ns), T): int32 counts."""
    C = probs.shape[1]
    has_col = (label_idx >= 0) & (label_idx < C) & mask
    true = jnp.where(has_col, label_idx, 0).astype(jnp.int32)[:, None]
    p_true = jnp.take_along_axis(probs, true, axis=1)
    ahead = (probs > p_true) | ((probs == p_true)
                                & (jnp.arange(C, dtype=jnp.int32)[None, :]
                                   < true))
    rank = ahead.sum(axis=1, dtype=jnp.int32)
    hit = jnp.stack([has_col & (rank < n) for n in top_ns])       # (N, n)
    made = (probs.max(axis=1)[:, None] >= thresholds[None, :]) \
        & mask[:, None]                                           # (n, T)
    w = has_col.astype(probs.dtype)
    ll = -(jnp.log(jnp.clip(p_true[:, 0], 1e-15, 1.0)) * w).sum()
    return {"LogLoss": ll / jnp.maximum(w.sum(), 1.0),
            "rows": mask.sum(dtype=jnp.int32),
            "hits": hit.sum(axis=1, dtype=jnp.int32),
            "made": made.sum(axis=0, dtype=jnp.int32),
            "correct": (hit[:, :, None] & made[None, :, :]).sum(
                axis=1, dtype=jnp.int32)}
