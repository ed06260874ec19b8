"""Tree model families: decision tree, random forest, gradient-boosted trees.

TPU-native replacement for the reference's SparkML tree wrappers and for its
XGBoost JNI dependency (reference: core/.../impl/classification/
OpDecisionTreeClassifier.scala, OpRandomForestClassifier.scala,
OpGBTClassifier.scala, OpXGBoostClassifier.scala and the impl/regression
variants; XGBoost native core per SURVEY §2.9).

Design — TPU-first, not a port of either Spark's RDD tree builder or
XGBoost's C++:

* **Histogram growth** (the XGBoost-hist / LightGBM algorithm): features are
  quantile-binned once into int32 bins (n_bins=32 — Spark's maxBins default);
  each tree level's split search is a (nodes, features, bins, stats)
  histogram, a cumsum over bins, and an argmax — all static shapes, all on
  device, no per-node host control flow.
* **MXU histograms, no scatters**: split search runs on a deterministic
  strided row sample (≤ _HIST_SAMPLE rows, weights rescaled by n/S — the
  XGBoost 'approx'/GOSS design point: split thresholds are order-statistic
  estimates and converge long before 65k rows), and each level's histogram
  is ONE matmul — (nodes⊗stats)ᵀ expanded against the int32 bin codes by
  the fused pallas kernel (histeng/kernels.py): the bin one-hot is built
  tile-by-tile in VMEM and never reaches HBM. Routing between levels is a
  *feature-select matmul*: the split feature's bin code is gathered by a
  (d, nodes) one-hot matmul and compared against the bin threshold —
  1/n_bins-th the FLOPs of a comparison-bit contraction.
* **Leaf statistics**: during the CV sweep, leaf values come from the
  split-search sample the grower already routed (free — a segment-sum of
  the sample's final node ids via the histogram kernel); the sweep only
  needs them to *score validation rows*, and the winner is refit with
  ``sweep=False`` where the FULL dataset is routed down the grown trees by
  the fused descent kernel (ops/forest.py) for EXACT served leaf values.
  Scatter-free end to end, so the whole builder tiles onto the MXU and
  scales to millions of rows.
* **Complete-heap trees of static depth**: arrays feat/thresh/leaf. A node
  that stops early keeps threshold +inf so every row routes left — training
  and serving follow identical routing with zero dynamic shapes. Empty
  descendant leaves are unreachable by construction.
* **The sweep**: hyperparameter × fold configurations run in
  ``_CFG_CHUNK_ELEMS``-bounded tree-batched chunks (one wide histogram
  matmul per tree level for the whole chunk) under an outer ``lax.map``;
  CV folds are 0/1 row weights exactly like the linear families.
* Binned routing and raw-value routing agree exactly: bin(x) = #{edges < x},
  so (bin > b) ⇔ (x > edges[b]) even with tied edges.
"""
from __future__ import annotations

import logging

from functools import lru_cache, partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from ..ops.forest import (
    _BLK_R, _T_CHAIN, chain_block_shape, forest_leaf_sums,
    forest_leaf_sums_chain, forest_predict, forest_predict_chain,
)
from ..histeng import build_hist, build_node_hist, pinned_row_sum
from ..histeng.kernels import _combine_form, _hist_shards, tree_lane_shape
from ..observability.trace import span as _obs_span
from .api import FittedParams, ModelFamily, register_family

N_BINS = 32  # Spark maxBins default (reference DefaultSelectorParams.MaxBin)

#: split-search sample cap: histograms are built from at most this many
#: evenly-strided rows (weights rescaled by n/S so count-based stopping
#: criteria keep full-data semantics); served leaf values use ALL rows
#: (exact refit pass), sweep-time leaf values use the sample.
_HIST_SAMPLE = 65536

#: sweep-time sample cap: CV candidates grow from a fraction of the refit
#: sample — split thresholds are order statistics and the CV ranking is
#: robust to the extra estimator noise (tests/test_round3_fixes.py holds
#: the sampled sweep's ranking against the exact one's); the refit winner
#: regrows at _HIST_SAMPLE. Each halving halves every growth histogram's
#: rows for the depth-12 default grids
_SWEEP_HIST_SAMPLE = 8192

#: sweep-time ensemble caps: CV candidates RANK with this many RF trees /
#: GBT boosting rounds — the metric is an ensemble-size-consistent estimate
#: (every config gets the same cap), the winner refits at its full
#: numTrees/maxIter through fit_batch(sweep=False). Same contract as the
#: split-search sample above
_SWEEP_RF_TREES = 16
_SWEEP_GBT_ROUNDS = 12


def _sweep_ensemble_cap(vals: np.ndarray, cap: int,
                        param: str) -> Optional[np.ndarray]:
    """Rank-consistent sweep-time ensemble capping.

    All configs equal (the default grids): clamp uniformly to ``cap`` — the
    CV estimate stays ensemble-size-consistent because every candidate gets
    the same budget. Distinct values (a custom grid sweeping ensemble size):
    a uniform clamp would fit every above-cap config byte-identically and
    selection among them would silently degenerate to grid order, so the
    sizes scale PROPORTIONALLY (max → cap, floor 1) instead, preserving the
    grid's relative budgets; the warning flags that ranking across ensemble
    sizes is then an approximation. Returns the capped per-config values, or
    None when no cap applies (all values ≤ cap)."""
    vals = np.asarray(vals, dtype=np.float64)
    vmax = float(vals.max())
    if vmax <= cap:
        return None
    if np.unique(vals).size == 1:
        return np.minimum(vals, float(cap))
    scaled = np.maximum(1.0, np.round(vals * (cap / vmax)))
    logger.warning(
        "custom grid sweeps %s over distinct values %s above the sweep "
        "ranking cap %d; candidates rank with proportionally scaled "
        "ensembles %s (a uniform cap would make them byte-identical and "
        "unrankable) and the winner refits at its full %s — an "
        "approximation when ranking across ensemble sizes. The "
        "validator's exact_sweep_fits=True (with max_eval_rows=None) ranks "
        "every candidate at its full size.",
        param, sorted(set(vals.tolist())), cap,
        sorted(set(scaled.tolist())), param)
    return scaled

#: config-chunk sizing: batch configurations together until the deepest
#: level's (sample rows x configs x trees x nodes) transient reaches this
#: element budget (~2 GB bf16), then lax.map over chunks — halving the
#: sweep sample therefore doubles the configs per chunk
_CFG_CHUNK_ELEMS = 1 << 30

#: trees per fused-descent call (ops/forest.py pallas cap)
_PREDICT_TREE_CHUNK = 128

#: chain-grower sibling subtraction pays off only for wide tree batches
#: (see the measurement note in _grow_forest_capped); below this width the
#: per-level reconstruction overhead exceeds the saved contraction
_CHAIN_SIBLING_MIN_TB = 128

#: per-level histogram element budget (f32): bounds the (Tb·nodes, d,
#: n_bins, k) split-search pipeline — XLA keeps ~3-6 of these alive
#: through the cumsum/gain chain, so ~1 GB per tensor keeps peak HBM well
#: inside a 16 GB chip even with that multiplier
_LEVEL_HIST_ELEMS = 1 << 28

#: element budget (f32) of the row-block partials of one flat histogram
#: build (histeng ``_hist_xla_pinned``: K blocks x stat columns x d·n_bins);
#: bounds the chunk only where there are more than two statistic planes
_HIST_PARTIAL_ELEMS = 1 << 30


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------

def _quantile_edges(X: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """Per-feature quantile bin edges, shape (d, n_bins-1)."""
    qs = jnp.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return jnp.quantile(X, qs, axis=0).T.astype(X.dtype)


def _bin_features(X: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """bin(x) = #{edges < x} ∈ [0, n_bins-1], shape (n, d) int32.

    Computed as a sum of broadcast comparisons — one fused elementwise pass
    (TPU sorts/searchsorted are far slower than n_bins comparisons)."""
    return (X[:, :, None] > edges[None, :, :]).sum(axis=2, dtype=jnp.int32)


def _sample_rows(n: int, cap: int = _HIST_SAMPLE) -> np.ndarray:
    """Deterministic strided sample indices for split search (static)."""
    if n <= cap:
        return np.arange(n)
    return np.linspace(0, n - 1, cap).astype(np.int64)


def _exact_leaf_stats(codes: jnp.ndarray, feat_heaps: jnp.ndarray,
                      bin_heaps: jnp.ndarray, stats: jnp.ndarray,
                      w: jnp.ndarray, depth: int, n_bins: int):
    """EXACT full-data leaf statistics via the fused descent kernel
    (ops/forest.py): route every row down T trees and accumulate stat sums
    per (tree, leaf) without any (n, T·m) HBM intermediate. Returns
    (T, L, k) stat sums and (T, L) weight sums. f32 end to end — leaf
    values are served predictions and must not inherit bf16 rounding."""
    T = feat_heaps.shape[0]
    aug = jnp.concatenate([stats * w[:, None], w[:, None]], axis=1)
    parts = []
    for lo in range(0, T, _PREDICT_TREE_CHUNK):
        hi = min(lo + _PREDICT_TREE_CHUNK, T)
        parts.append(forest_leaf_sums(
            codes, feat_heaps[lo:hi], bin_heaps[lo:hi], aug,
            depth=depth, n_bins=n_bins))
    out = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return out[..., :-1], out[..., -1]


@jax.named_scope("hist.split")
def _split_gain(SL, SR, total, cfg, mode: str):
    """Gain + validity for every candidate split.

    SL/SR: (m, d, n_bins-1, k) left/right stats; total: (m, k); cfg values
    are scalars (per-config growth under vmap) or (m,) arrays (the
    tree-batched grower, one entry per heap node).
    mode 'gh': stats = [grad, hess, count] — XGBoost-style Newton gain,
    normalized by parent count so min_info_gain is scale-free (matches the
    variance-impurity gain Spark compares against minInfoGain).
    mode 'counts': stats = per-class weighted counts — Gini gain.
    """
    def bc(v):  # broadcast a scalar or (m,) cfg entry over (m, d, nb-1)
        v = jnp.asarray(v)
        return v[:, None, None] if v.ndim == 1 else v

    if mode == "gh":
        lam_v = jnp.asarray(cfg["lam"])          # scalar or (m,)
        lam = bc(lam_v)
        GL, HL, CL = SL[..., 0], SL[..., 1], SL[..., 2]
        GR, HR, CR = SR[..., 0], SR[..., 1], SR[..., 2]
        GP, HP, CP = total[:, 0], total[:, 1], total[:, 2]

        def score(G, H, l):
            return G * G / (H + l + 1e-12)

        raw = (score(GL, HL, lam) + score(GR, HR, lam)
               - score(GP, HP, lam_v)[:, None, None])
        gain = raw / jnp.maximum(CP, 1.0)[:, None, None]
        mcw = bc(cfg["min_child_weight"])
        mi = jnp.maximum(bc(cfg["min_instances"]), 1e-6)
        valid = (CL >= mi) & (CR >= mi) & (HL >= mcw) & (HR >= mcw)
        return gain, valid
    # Gini (classification trees)
    wL = SL.sum(-1)
    wR = SR.sum(-1)
    wP = total.sum(-1)

    def gini(S, W):
        p = S / jnp.maximum(W, 1e-12)[..., None]
        return 1.0 - (p * p).sum(-1)

    impP = gini(total, wP)[:, None, None]
    wPn = jnp.maximum(wP, 1e-12)[:, None, None]
    gain = impP - (wL / wPn) * gini(SL, wL) - (wR / wPn) * gini(SR, wR)
    mi = jnp.maximum(bc(cfg["min_instances"]), 1e-6)
    valid = (wL >= mi) & (wR >= mi)
    return gain, valid


def _grow_tree(codes_s, edges, stats_s, w_s, feat_mask, cfg, *,
               depth: int, n_bins: int, mode: str):
    """Grow one complete-heap tree on the split-search sample.

    codes_s: (S, d) int32 bin codes (shared across trees/configs);
    stats_s: (S, k) per-row stat vector; w_s: (S,) row weights (folds ×
    bootstrap, pre-scaled by n/S); feat_mask: (d,) bool; cfg: traced scalars
    {max_depth, min_instances, min_info_gain, lam, min_child_weight}.

    Each level's histogram is ONE fused one-hot matmul — (node-one-hot ⊗
    weighted stats)ᵀ expanded against the bin codes (hist_matmul,
    histeng/kernels.py; the bin one-hot never reaches HBM on the pallas path)
    — and sample routing is a plain-XLA feature-select matmul: a (d, m)
    one-hot of the chosen split features gathers each node's bin code for
    an elementwise threshold compare. Batches under vmap over trees/configs
    (GBT's per-round trees); the heavily-batched DT/RF sweeps use the
    tree-batched `_grow_forest` instead, whose flattened lane layout avoids
    the tiny-minor-dim arrays vmap produces here. Returns (feat_heap
    (2^D−1,), thresh_heap (2^D−1,), bin_heap (2^D−1,) int32 with sentinel
    n_bins for non-splits, node_s (S,) final sample leaf assignment).
    """
    S = codes_s.shape[0]
    d = feat_mask.shape[0]
    k = stats_s.shape[1]
    sw = (stats_s * w_s[:, None]).astype(jnp.bfloat16)      # (S, k)
    codes_f = codes_s.astype(jnp.bfloat16)  # bin codes < 256: exact in bf16
    feat_heap = jnp.zeros((2 ** depth - 1,), jnp.int32)
    thr_heap = jnp.full((2 ** depth - 1,), jnp.inf, dtype=jnp.float32)
    bin_heap = jnp.full((2 ** depth - 1,), n_bins, dtype=jnp.int32)
    node = jnp.zeros((S,), jnp.int32)
    # each level runs at its NATURAL node width m = 2^level (half the
    # padded-to-deepest FLOPs summed over levels); under vmap the batch axis
    # widens the histogram's stat columns, one kernel call per level for the
    # whole chunk
    for level in range(depth):
        m = 2 ** level
        n_oh = (node[:, None]
                == jnp.arange(m, dtype=jnp.int32)).astype(jnp.bfloat16)
        A = (n_oh[:, :, None] * sw[:, None, :]).reshape(S, m * k)
        hist = build_hist(codes_s, A, n_bins)
        hist = hist.reshape(m, k, d, n_bins).transpose(0, 2, 3, 1)
        cum = jnp.cumsum(hist, axis=2)
        total = cum[:, 0, -1, :]                      # (m, k) node totals
        SL = cum[:, :, :-1, :]                        # split "bin <= b"
        SR = total[:, None, None, :] - SL
        gain, valid = _split_gain(SL, SR, total, cfg, mode)
        valid = valid & feat_mask[None, :, None]
        gain = jnp.where(valid, gain, -jnp.inf)
        gflat = gain.reshape(m, d * (n_bins - 1))
        best = jnp.argmax(gflat, axis=1)
        bf = (best // (n_bins - 1)).astype(jnp.int32)
        bb = (best % (n_bins - 1)).astype(jnp.int32)
        bgain = jnp.take_along_axis(gflat, best[:, None], axis=1)[:, 0]
        active = jnp.asarray(level, jnp.float32) < cfg["max_depth"]
        do_split = active & jnp.isfinite(bgain) & (bgain > cfg["min_info_gain"])
        thr = jnp.where(do_split, edges[bf, bb], jnp.inf).astype(jnp.float32)
        feat_heap = feat_heap.at[m - 1: 2 * m - 1].set(
            jnp.where(do_split, bf, 0))
        thr_heap = thr_heap.at[m - 1: 2 * m - 1].set(thr)
        bb_eff = jnp.where(do_split, bb, n_bins)
        bin_heap = bin_heap.at[m - 1: 2 * m - 1].set(bb_eff)
        # feature-select routing: gather each node's split-feature code by a
        # (d, m) one-hot matmul, compare against the bin threshold (sentinel
        # n_bins ⇒ never greater ⇒ route left), pick the row's node via the
        # n_oh mask already built for the histogram
        f_sel = (jnp.where(do_split, bf, 0)[None, :]
                 == jnp.arange(d, dtype=jnp.int32)[:, None]
                 ).astype(jnp.bfloat16)                          # (d, m)
        code_sel = codes_f @ f_sel                               # (S, m)
        go_m = (code_sel > bb_eff.astype(jnp.bfloat16)
                ).astype(jnp.bfloat16)
        go = jnp.sum(go_m * n_oh, axis=1) > 0.5
        node = 2 * node + go.astype(jnp.int32)
    return feat_heap, thr_heap, bin_heap, node


def _tree_columns(codes_s, fmasks, feat_idx, n_bins: int):
    """The columns a forest grower builds histograms of and searches.

    Without ``feat_idx`` every tree sees all d columns of the shared codes
    and ``fmasks`` (Tb, d) marks its candidates. With ``feat_idx`` (Tb,
    d_sub), each tree's drawn columns in ascending order (so ``argmax`` ties
    break as over all d) and padded with the sentinel d, each tree gets its
    own COMPACT codes (S, Tb, d_sub), made once for all levels by a one-hot
    (d+1, Tb·d_sub) matmul (one nonzero term a sum: exact in bfloat16 for
    codes <= n_bins <= 256); the sentinel selects an appended column of
    ``n_bins``, the code that matches no histogram lane. A tree's FIRST
    compact column always holds a real one (column 0, not a candidate,
    where the tree drew nothing): the growers read node totals off it.
    Returns (codes for `build_node_hist`, (Tb, width) bool candidates,
    (Tb, width) true column of each compact one or None)."""
    if feat_idx is None:
        return codes_s, fmasks, None
    S, d = codes_s.shape
    Tb, d_sub = feat_idx.shape
    drawn = feat_idx < d
    col_of = jnp.where(drawn, feat_idx, 0)
    src = feat_idx.at[:, 0].set(col_of[:, 0])
    codes_aug = jnp.concatenate(
        [codes_s.astype(jnp.bfloat16),
         jnp.full((S, 1), n_bins, jnp.bfloat16)], axis=1)        # (S, d+1)
    pick = (src.reshape(1, Tb * d_sub)
            == jnp.arange(d + 1, dtype=jnp.int32)[:, None]
            ).astype(jnp.bfloat16)                               # (d+1, ·)
    codes_c = jnp.dot(codes_aug, pick, preferred_element_type=jnp.float32
                      ).astype(jnp.int32).reshape(S, Tb, d_sub)
    return codes_c, drawn, col_of


def _grow_forest(codes_s, edges, sw_list, fmasks, cfg, *, depth: int,
                 n_bins: int, mode: str, return_leaf_stats: bool = False,
                 feat_idx=None):
    """Grow Tb complete-heap trees AT ONCE on the split-search sample.

    The tree batch (configs × trees) lives flattened in the lane axis from
    end to end — every intermediate is (S, m·Tb)-shaped (j-major: lane =
    j·Tb + t) with a large minor dimension, because TPU arrays pad the
    minor-most dim to 128 lanes and a (S, Tb, k≈2) layout wastes 64× HBM
    (measured OOM under the vmapped per-tree grower). J-major keeps every
    per-tree group reduction a free (S, m, Tb) reshape + axis-1 sum.

    codes_s: (S, d) shared int32 bin codes; sw_list: k arrays (S, Tb) — the
    per-tree stat·rowweight products, one array per stat so no tiny-minor
    array ever exists; fmasks: (Tb, d) feature subsets; ``feat_idx``: the
    same subsets as a (Tb, d_sub) index table where they are strict (see
    `_tree_columns`: histograms and split search then run over each tree's
    d_sub columns only); cfg: dict of (Tb,)
    per-tree scalars. Returns (feat (Tb,H), thresh (Tb,H), bins (Tb,H),
    node_s (S, Tb)); with ``return_leaf_stats`` also a (Tb, 2^depth, k)
    per-leaf stat-sum tensor read off the FINAL level's histogram — the
    chosen split's left cumsum is the left child's total and the right
    child is the node total minus it, so sweep-time leaf values cost no
    extra histogram pass (stopped nodes route everything left)."""
    S, d = codes_s.shape
    Tb = sw_list[0].shape[1]
    k = len(sw_list)
    codes_f = codes_s.astype(jnp.bfloat16)
    codes_h, col_ok, col_of = _tree_columns(codes_s, fmasks, feat_idx, n_bins)
    dw = col_ok.shape[1]                 # columns searched per tree
    H = 2 ** depth - 1
    feat_heap = jnp.zeros((Tb, H), jnp.int32)
    thr_heap = jnp.full((Tb, H), jnp.inf, jnp.float32)
    bin_heap = jnp.full((Tb, H), n_bins, jnp.int32)
    node = jnp.zeros((S, Tb), jnp.int32)
    hist_prev = None
    # depth 0: one root leaf per tree, stats are the plain column sums
    leaf_stats = jnp.stack(
        [pinned_row_sum(s.astype(jnp.float32), axis=0) for s in sw_list],
        axis=-1)[:, None, :]                                # (Tb, 1, k)
    for level in range(depth):
        m = 2 ** level
        M = Tb * m
        # lane layout J-MAJOR: lane = j*Tb + t, i.e. a (S, M) array is a
        # no-copy reshape of (S, m, Tb) — the per-tree group sums in the
        # routing step become an axis-1 reduction over sublane groups
        # instead of a dense (S, M) @ (M, Tb) block-diagonal matmul.
        # Sibling subtraction (the LightGBM/XGBoost-hist trick): per-tree
        # row weights are constant across levels and a node's children
        # partition its rows exactly, so only the LEFT child of every node
        # needs a histogram — the right child is parent − left. Halves the
        # histogram matmul FLOPs and the A_cat HBM traffic at every level.
        if level == 0:
            # root: node == 0 everywhere, the one-hot is all-ones
            hist = build_node_hist(codes_h, node, sw_list, n_bins, n_nodes=1)
            hist = hist[:, 0].transpose(1, 2, 3, 0)
        else:
            h = m // 2
            # left children only (heap slot 2j), fused in VMEM
            # (node_hist_matmul stride=2); right = parent − left below
            hist_l = build_node_hist(codes_h, node, sw_list, n_bins,
                                     n_nodes=h, stride=2)
            hist_l = hist_l.reshape(k, h * Tb, dw, n_bins
                                    ).transpose(1, 2, 3, 0)          # (h·Tb,…)
            hist_r = hist_prev - hist_l
            # interleave children j-major: row (2j'+parity)·Tb + t
            hist = jnp.stack(
                [hist_l.reshape(h, Tb, dw, n_bins, k),
                 hist_r.reshape(h, Tb, dw, n_bins, k)],
                axis=1).reshape(M, dw, n_bins, k)
        hist_prev = hist
        cum = jnp.cumsum(hist, axis=2)
        total = cum[:, 0, -1, :]                       # (M, k) node totals
        SL = cum[:, :, :-1, :]
        SR = total[:, None, None, :] - SL
        cfg_m = {key: jnp.tile(v, m) for key, v in cfg.items()}
        gain, valid = _split_gain(SL, SR, total, cfg_m, mode)
        valid = valid & jnp.tile(col_ok, (m, 1))[:, :, None]
        gain = jnp.where(valid, gain, -jnp.inf)
        gflat = gain.reshape(M, dw * (n_bins - 1))
        best = jnp.argmax(gflat, axis=1)
        bf = (best // (n_bins - 1)).astype(jnp.int32)
        if col_of is not None:           # compact column -> the tree's own
            bf = jnp.take_along_axis(jnp.tile(col_of, (m, 1)), bf[:, None],
                                     axis=1)[:, 0]
        bb = (best % (n_bins - 1)).astype(jnp.int32)
        bgain = jnp.take_along_axis(gflat, best[:, None], axis=1)[:, 0]
        active = jnp.asarray(level, jnp.float32) < jnp.tile(
            cfg["max_depth"], m)
        do_split = active & jnp.isfinite(bgain) & (bgain > cfg_m["min_info_gain"])
        bf_eff = jnp.where(do_split, bf, 0)
        bb_eff = jnp.where(do_split, bb, n_bins)
        thr = jnp.where(do_split, edges[bf, bb], jnp.inf).astype(jnp.float32)
        # j-major (M,) -> heap order (Tb, m)
        feat_heap = feat_heap.at[:, m - 1: 2 * m - 1].set(
            bf_eff.reshape(m, Tb).T)
        thr_heap = thr_heap.at[:, m - 1: 2 * m - 1].set(
            thr.reshape(m, Tb).T)
        bin_heap = bin_heap.at[:, m - 1: 2 * m - 1].set(
            bb_eff.reshape(m, Tb).T)
        # feature-select routing: gather each node's split-feature code by a
        # (d, M) one-hot matmul, compare against the bin threshold (sentinel
        # n_bins ⇒ route left), select the row's node via the j-major node
        # one-hot and reduce the j axis as an (S, m, Tb) sublane sum
        sel = (bf_eff[None, :] == jnp.arange(d, dtype=jnp.int32)[:, None]
               ).astype(jnp.bfloat16)                             # (d, M)
        code_sel = codes_f @ sel                                  # (S, M)
        go_lane = (code_sel > bb_eff.astype(jnp.bfloat16)
                   ).astype(jnp.bfloat16)
        j_all = jnp.arange(m, dtype=jnp.int32)[None, :, None]
        n_oh = (node[:, None, :] == j_all).astype(jnp.bfloat16)   # (S, m, Tb)
        go = (go_lane.reshape(S, m, Tb) * n_oh).sum(axis=1)       # (S, Tb)
        node = 2 * node + (go > jnp.bfloat16(0.5)).astype(jnp.int32)
        if return_leaf_stats and level == depth - 1:
            # leaf stats off this level's histogram: left child = chosen
            # split's left cumsum (node total when stopped), right = rest
            k_st = hist.shape[-1]
            SL_flat = SL.reshape(M, dw * (n_bins - 1), k_st)
            left = jnp.take_along_axis(
                SL_flat, best[:, None, None], axis=1)[:, 0]       # (M, k)
            left = jnp.where(do_split[:, None], left, total)
            right = total - left
            # j-major rows (j·Tb + t) → (Tb, L=2m, k), leaf id = 2j + parity
            leaf_stats = jnp.stack(
                [left.reshape(m, Tb, k_st), right.reshape(m, Tb, k_st)],
                axis=1).transpose(2, 0, 1, 3).reshape(Tb, 2 * m, k_st)
    if return_leaf_stats:
        return feat_heap, thr_heap, bin_heap, node, leaf_stats
    return feat_heap, thr_heap, bin_heap, node


def _grow_forest_capped(codes_s, edges, sw_list, fmasks, cfg, *, depth: int,
                        n_bins: int, mode: str, n_slots: int, feat_idx=None):
    """Grow Tb slot-chain ("leaf budget") trees at once — arbitrary depth at
    a bounded per-level width.

    The complete-heap grower's per-level histogram is (2^level·Tb, d, nb, k),
    which caps practical depth at ~8; the reference's default grids sweep
    maxDepth 12 (DefaultSelectorParams.scala:37). Here each level holds at
    most ``n_slots`` live nodes: every valid candidate split is ranked by
    gain per tree and the top (budget) splits are performed — each split
    adds exactly one net slot, so ``n_slots`` is precisely a leaf budget
    (the XGBoost 'lossguide' / LightGBM num_leaves design point). Unsplit
    nodes carry forward as leaves (they keep competing at later levels, and
    re-lose deterministically once stopped — same rows ⇒ same gain). Slots
    are compact by construction: level l holds slots [0, n_live_t) with
    n_live ≤ min(2^l, n_slots).

    Emits per-level tables (Tb, depth, W): split feature, bin threshold
    (sentinel ``n_bins`` ⇒ route left), raw threshold, and the child base
    pointer — routing is ``slot' = base[slot] + go`` (ops/forest.py chain
    kernels). ``feat_idx``: as in `_grow_forest`. Returns (feat_lv, thr_lv,
    bin_lv, base_lv, node_s) with
    node_s (S, Tb) the final sample leaf slot in [0, min(2^depth, W))."""
    from ..ops.forest import _chain_widths, _check_slots
    _check_slots(n_slots)
    S, d = codes_s.shape
    Tb = sw_list[0].shape[1]
    k = len(sw_list)
    W = n_slots
    codes_f = codes_s.astype(jnp.bfloat16)
    codes_h, col_ok, col_of = _tree_columns(codes_s, fmasks, feat_idx, n_bins)
    dw = col_ok.shape[1]                 # columns searched per tree
    feat_lv = jnp.zeros((Tb, depth, W), jnp.int32)
    thr_lv = jnp.full((Tb, depth, W), jnp.inf, jnp.float32)
    bin_lv = jnp.full((Tb, depth, W), n_bins, jnp.int32)
    base_lv = jnp.zeros((Tb, depth, W), jnp.int32)
    node = jnp.zeros((S, Tb), jnp.int32)          # slot at current level
    n_live = jnp.ones((Tb,), jnp.int32)
    widths = _chain_widths(depth, W)
    # sibling subtraction, chain edition (the heap grower's LightGBM trick
    # adapted to slot-chain trees): per-tree row weights are constant
    # across levels, so a freshly-computed histogram is only needed for
    # EVEN slots (node_hist_matmul stride=2 — halves the dominant
    # contraction). Odd slots reconstruct from the previous level: a right
    # child (its parent was kept, child base even) is parent − left
    # sibling; a carried slot landing on an odd position keeps its old
    # histogram verbatim. The (j_src, is_rchild) odd-slot inverse mapping
    # is built from the level's kept/carried/base tables; dead slots
    # (≥ n_live) may carry garbage but are masked out of every split
    # decision (`live`) and are never sourced by kept/carried.
    # MEASURED (v5e, S=16384, d=64, nb=32, W=64): wins only when the tree
    # batch is wide enough for the halved contraction to stay MXU-bound —
    # RF sweep chunks (Tb=500) −8%, GBT's Tb=54 boosting scan +17% (its
    # narrow per-step ops are latency-bound; the reconstruction's extra
    # gathers/stacks cost more than the saved FLOPs), hence the width gate.
    sibling = Tb >= _CHAIN_SIBLING_MIN_TB
    hist5_prev = None                 # (Wl_prev, Tb, dw, nb, k) f32
    odd_map_prev = None               # (j_src (Wh_o, Tb), is_rchild)
    for level in range(depth):
        Wl = widths[level]
        Wn = widths[level + 1] if level + 1 < depth else min(2 ** depth, W)
        M = Wl * Tb
        # node-histogram contraction (histeng.build_node_hist):
        # XLA's pipelined A_cat contraction — a pallas kernel that expanded
        # the operand in VMEM measured slower at every production shape and
        # was retired
        if level == 0 or Wl % 2 or not sibling:
            hist5 = build_node_hist(codes_h, node, sw_list, n_bins,
                                    n_nodes=Wl).transpose(1, 2, 3, 4, 0)
        else:
            Wh = Wl // 2
            he5 = build_node_hist(codes_h, node, sw_list, n_bins,
                                  n_nodes=Wh, stride=2
                                  ).transpose(1, 2, 3, 4, 0)   # slot 2j'
            j_src, is_rch = odd_map_prev
            prev_flat = hist5_prev.reshape(
                hist5_prev.shape[0], Tb, dw * n_bins * k)
            src = jnp.take_along_axis(
                prev_flat.transpose(1, 0, 2),             # (Tb, Wl_prev, ·)
                j_src.T[:, :, None].astype(jnp.int32), axis=1
            ).transpose(1, 0, 2).reshape(Wh, Tb, dw, n_bins, k)
            odd5 = src - jnp.where(
                is_rch[:, :, None, None, None], he5,
                jnp.zeros_like(he5))
            hist5 = jnp.stack([he5, odd5], axis=1).reshape(
                Wl, Tb, dw, n_bins, k)
        hist5_prev = hist5
        hist = hist5.reshape(M, dw, n_bins, k)
        cum = jnp.cumsum(hist, axis=2)
        total = cum[:, 0, -1, :]                       # (M, k) node totals
        SL = cum[:, :, :-1, :]
        SR = total[:, None, None, :] - SL
        cfg_m = {key: jnp.tile(v, Wl) for key, v in cfg.items()}
        gain, valid = _split_gain(SL, SR, total, cfg_m, mode)
        valid = valid & jnp.tile(col_ok, (Wl, 1))[:, :, None]
        gain = jnp.where(valid, gain, -jnp.inf)
        gflat = gain.reshape(M, dw * (n_bins - 1))
        best = jnp.argmax(gflat, axis=1)
        bf = (best // (n_bins - 1)).astype(jnp.int32)
        if col_of is not None:           # compact column -> the tree's own
            bf = jnp.take_along_axis(jnp.tile(col_of, (Wl, 1)), bf[:, None],
                                     axis=1)[:, 0]
        bb = (best % (n_bins - 1)).astype(jnp.int32)
        bgain = jnp.take_along_axis(gflat, best[:, None], axis=1)[:, 0]
        active = jnp.asarray(level, jnp.float32) < jnp.tile(
            cfg["max_depth"], Wl)
        cand = active & jnp.isfinite(bgain) & (bgain > cfg_m["min_info_gain"])
        # live slots are [0, n_live) per tree; dead lanes must not split
        j_2d = jnp.arange(Wl, dtype=jnp.int32)[:, None]          # (Wl, 1)
        live = j_2d < n_live[None, :]                            # (Wl, Tb)
        cand_2d = cand.reshape(Wl, Tb) & live
        # leaf-budget cap: each split adds one net slot, so at most
        # q = W_next − n_live splits may run this level; keep the q best
        # by gain. Rank by COUNTING dominating slots — a (Wl, Wl, Tb)
        # comparison reduction — instead of a double argsort: XLA's sort
        # costs ~ms per call at these shapes while the count is one
        # elementwise pass; ties break by slot index ascending, identical
        # to a stable descending argsort
        key = jnp.where(cand_2d, bgain.reshape(Wl, Tb), -jnp.inf)
        k_i = key[:, None, :]                                    # (Wl,1,Tb)
        k_j = key[None, :, :]                                    # (1,Wl,Tb)
        j_lt_i = (jnp.arange(Wl)[None, :, None]
                  < jnp.arange(Wl)[:, None, None])
        dominates = (k_j > k_i) | ((k_j == k_i) & j_lt_i)
        rank = dominates.sum(axis=1).astype(jnp.int32)           # (Wl, Tb)
        q = jnp.maximum(Wn - n_live, 0)[None, :]
        kept = cand_2d & (rank < q)
        n_split = kept.sum(axis=0).astype(jnp.int32)             # (Tb,)
        # child base for kept splits: 2·gain-rank (kept ⊆ top-q candidates,
        # so their candidate rank IS their split rank); carried live slots
        # land after the children in slot order
        carried = live & ~kept
        c_rank = jnp.cumsum(carried.astype(jnp.int32), axis=0) - 1
        base_2d = jnp.where(
            kept, 2 * rank,
            jnp.where(carried, 2 * n_split[None, :] + c_rank, 0))
        if sibling and level + 1 < depth and widths[level + 1] % 2 == 0:
            # odd-slot inverse map for the next level's sibling
            # subtraction: odd slot i sources prev slot j where either j
            # was kept and its right child landed at i (base+1 == i), or
            # j carried onto i (base == i). Targets are unique, so the
            # one-hot · j sum IS the inverse permutation.
            wh_n = widths[level + 1] // 2
            i_odd = (1 + 2 * jnp.arange(wh_n, dtype=jnp.int32)
                     )[None, :, None]                       # (1, wh_n, 1)
            oh_r = (jnp.where(kept, base_2d + 1, -1)[:, None, :]
                    == i_odd)                               # (Wl, wh_n, Tb)
            oh_c = (jnp.where(carried, base_2d, -1)[:, None, :]
                    == i_odd)
            j_idx = jnp.arange(Wl, dtype=jnp.int32)[:, None, None]
            odd_map_prev = (((oh_r | oh_c) * j_idx).sum(axis=0),
                            oh_r.any(axis=0))               # (wh_n, Tb) ×2
        kept_f = kept.reshape(M)
        bf_eff = jnp.where(kept_f, bf, 0)
        bb_eff = jnp.where(kept_f, bb, n_bins)
        thr = jnp.where(kept_f, edges[bf, bb], jnp.inf).astype(jnp.float32)
        # j-major (M,) → (Tb, Wl) table rows
        feat_lv = feat_lv.at[:, level, :Wl].set(bf_eff.reshape(Wl, Tb).T)
        thr_lv = thr_lv.at[:, level, :Wl].set(thr.reshape(Wl, Tb).T)
        bin_lv = bin_lv.at[:, level, :Wl].set(bb_eff.reshape(Wl, Tb).T)
        base_lv = base_lv.at[:, level, :Wl].set(base_2d.T)
        # route: slot' = base[slot] + go (sentinel bin ⇒ go 0); base ≤ W−1
        # and W ≤ 256, so the bf16 lane accumulation is exact
        sel = (bf_eff[None, :] == jnp.arange(d, dtype=jnp.int32)[:, None]
               ).astype(jnp.bfloat16)                             # (d, M)
        code_sel = codes_f @ sel                                  # (S, M)
        go_lane = (code_sel > bb_eff.astype(jnp.bfloat16)
                   ).astype(jnp.bfloat16)
        val_lane = go_lane + base_2d.reshape(M).astype(jnp.bfloat16)[None, :]
        j_all = jnp.arange(Wl, dtype=jnp.int32)[None, :, None]
        n_oh = (node[:, None, :] == j_all).astype(jnp.bfloat16)   # (S, Wl, Tb)
        nxt = (val_lane.reshape(S, Wl, Tb) * n_oh).sum(axis=1)    # (S, Tb)
        node = jnp.round(nxt.astype(jnp.float32)).astype(jnp.int32)
        n_live = n_live + n_split
    return feat_lv, thr_lv, bin_lv, base_lv, node


_DIAG_BLOCK = 64
#: the narrowest block of trees the leaf sums are cut into: the histogram
#: kernel's code block is one column a tree, and the chip's compiler takes
#: none under 4 columns at 64 leaves and more (the lane repeat of a 1- or
#: 2-column block asks 29-58 MB of VMEM for 16: compiled for a described
#: v5e, PR 43)
_DIAG_MIN_BLOCK = 4


def _diag_leaf_hist(node_s: jnp.ndarray, A_cols: jnp.ndarray,
                    L: int) -> jnp.ndarray:
    """out[j, t, l] = Σ_s A_cols[s, j, t]·1[node_s[s, t] == l] — per-tree
    segment-sums through the histogram kernel (trees as 'features', leaves
    as 'bins'), diagonal extracted. ``A_cols``: (S, Tb) for one stat — or
    (S, J, Tb) to reduce J stats against the same trees in ONE kernel call
    (GBT's G and H sums). Blocked in groups of trees so the cross-tree waste
    stays a constant factor (full-width would be quadratic in the tree
    count): `_DIAG_BLOCK` trees a group, and where there are 32 or fewer the
    power of two at or above their count (`tree_lane_shape`'s), no narrower
    than `_DIAG_MIN_BLOCK`. A boosted refit's ONE tree a round is then a
    (4 J, S) x (S, 4 L) product, not 64 trees against 64 x L leaves."""
    squeeze = A_cols.ndim == 2
    if squeeze:
        A_cols = A_cols[:, None, :]
    S, J, Tb = A_cols.shape
    g = min(_DIAG_BLOCK, max(_DIAG_MIN_BLOCK, tree_lane_shape(Tb)[0]))
    Tp = -(-Tb // g) * g
    if Tp != Tb:  # sentinel code L matches no leaf; zero stat columns
        node_s = jnp.pad(node_s, ((0, 0), (0, Tp - Tb)), constant_values=L)
        A_cols = jnp.pad(A_cols, ((0, 0), (0, 0), (0, Tp - Tb)))
    outs = []
    for lo in range(0, Tp, g):
        blk = A_cols[:, :, lo:lo + g].reshape(S, J * g)     # stat-major rows
        full = build_hist(node_s[:, lo:lo + g], blk, L,
                          exact=True)                      # (J*g, g*L)
        full = full.reshape(J, g, g, L)
        outs.append(full[:, jnp.arange(g), jnp.arange(g)])  # (J, g, L)
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    out = out[:, :Tb]
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# Batched fit drivers (chunked vmap over configurations)
# ---------------------------------------------------------------------------


def _class_leaf(leaf_stats, leaf_w):
    """Per-leaf class probabilities from weighted counts."""
    tot = jnp.maximum(leaf_stats.sum(-1, keepdims=True), 1e-12)
    return leaf_stats / tot


def _mean_leaf(leaf_stats, leaf_w):
    """gh-mode with g=-y, h=1: Newton leaf -G/H = weighted mean of y."""
    return -leaf_stats[:, 0] / jnp.maximum(leaf_stats[:, 1], 1e-12)


def _make_stats(y, num_classes: int, task: str):
    if task == "classification":
        return jax.nn.one_hot(y.astype(jnp.int32), num_classes,
                              dtype=jnp.float32), "counts"
    ones = jnp.ones_like(y)
    return jnp.stack([-y, ones, ones], axis=1), "gh"


def _prep_tree_inputs(X, y, n_bins, num_classes, task, full_bin=True,
                      sweep=False):
    """Shared per-fit prep: sampled edges, full + sampled int32 bin codes
    (the operands of the fused histogram/routing kernels), per-row stats,
    and the n/S weight rescale. ``full_bin`` skips binning the full dataset
    for fits that never touch it (GBT trains entirely on the sample).
    ``sweep`` halves the split-search sample (_SWEEP_HIST_SAMPLE)."""
    n = X.shape[0]
    samp = jnp.asarray(_sample_rows(
        n, _SWEEP_HIST_SAMPLE if sweep else _HIST_SAMPLE))
    Xs = X[samp]
    edges = _quantile_edges(Xs, n_bins)
    if full_bin:
        binned = _bin_features(X, edges)
        binned_s = binned[samp]
    else:
        binned = None
        binned_s = _bin_features(Xs, edges)
    stats, mode = _make_stats(y, num_classes, task)
    w_scale = jnp.asarray(n / samp.shape[0], X.dtype)
    return samp, edges, binned, binned_s, stats, mode, w_scale


def _exact_leaf_stats_chain(codes, feat_lv, bin_lv, base_lv, stats,
                            w: jnp.ndarray, n_bins: int):
    """Chain-format analog of :func:`_exact_leaf_stats` (full-data leaf
    sums via the fused chain descent kernel, f32 end to end)."""
    aug = jnp.concatenate([stats * w[:, None], w[:, None]], axis=1)
    out = forest_leaf_sums_chain(codes, feat_lv, bin_lv, base_lv, aug,
                                 n_bins=n_bins)
    return out[..., :-1], out[..., -1]


@partial(jax.jit, static_argnames=("depth", "n_bins", "num_classes", "task",
                                   "sweep", "n_slots"))
def _fit_dt_batch(X, y, weights, max_depth, min_inst, min_gain, *,
                  depth, n_bins, num_classes, task, sweep=False, n_slots=0):
    d = X.shape[1]
    B = weights.shape[0]
    samp, edges, binned, binned_s, stats, mode, w_scale = \
        _prep_tree_inputs(X, y, n_bins, num_classes, task,
                          full_bin=not sweep, sweep=sweep)
    stats_s = stats[samp]                                   # (S, k)
    k = stats.shape[1]
    deep = n_slots > 0
    L = min(2 ** depth, n_slots) if deep else 2 ** depth
    lane_w = (min(2 ** (depth - 1), n_slots) * k if deep
              else 2 ** (depth - 1))
    cb = max(1, min(B, _CFG_CHUNK_ELEMS // (binned_s.shape[0] * lane_w)))

    def one_chunk(w_c, md, mi, mg):
        """Grow cb single-tree configs in one tree-batched forest call."""
        w_bs = w_c[:, samp].T * w_scale                     # (S, cb)
        sw_list = [stats_s[:, k_i][:, None] * w_bs
                   for k_i in range(stats_s.shape[1])]
        cfg = {"max_depth": md, "min_instances": mi, "min_info_gain": mg,
               "lam": jnp.full((cb,), 1e-6, jnp.float32),
               "min_child_weight": jnp.zeros((cb,), jnp.float32)}
        if deep:
            fs, ths, bhs, abs_, node_s = _grow_forest_capped(
                binned_s, edges, sw_list, jnp.ones((cb, d), bool), cfg,
                depth=depth, n_bins=n_bins, mode=mode, n_slots=n_slots)
        else:
            fs, ths, bhs, node_s = _grow_forest(
                binned_s, edges, sw_list, jnp.ones((cb, d), bool), cfg,
                depth=depth, n_bins=n_bins, mode=mode)
            abs_ = jnp.zeros((cb, 0), jnp.int32)
        if sweep:  # sample leaf stats (validation scoring only)
            aug_cols = sw_list + [w_bs]
            sums = jnp.stack(
                [_diag_leaf_hist(node_s, c.astype(jnp.float32), L)
                 for c in aug_cols], axis=-1)               # (cb, L, k+1)
            ls, lw = sums[..., :-1], sums[..., -1]
            leaf_c = (jax.vmap(_class_leaf)(ls, lw)
                      if task == "classification"
                      else jax.vmap(_mean_leaf)(ls, lw)[:, :, None])
        else:
            leaf_c = jnp.zeros(
                (cb, L, stats.shape[1] if task == "classification" else 1),
                jnp.float32)
        return fs, ths, bhs, abs_, leaf_c

    n_chunks = -(-B // cb)
    B_pad = n_chunks * cb
    args = (weights, max_depth, min_inst, min_gain)
    if B_pad != B:
        idx = jnp.arange(B_pad) % B
        args = jax.tree_util.tree_map(lambda a: a[idx], args)
    args = jax.tree_util.tree_map(
        lambda a: a.reshape((n_chunks, cb) + a.shape[1:]), args)
    feat, thr, bheap, bases, leaf = jax.lax.map(
        lambda ch: one_chunk(*ch), args)
    feat, thr, bheap, bases, leaf = jax.tree_util.tree_map(
        lambda a: a.reshape((B_pad,) + a.shape[2:])[:B],
        (feat, thr, bheap, bases, leaf))

    if not sweep:  # EXACT full-data leaf stats via the fused descent kernel
        def leaf_one(args):
            if deep:
                f, bh, ab, w = args
                ls, lw = _exact_leaf_stats_chain(
                    binned, f[None], bh[None], ab[None], stats, w, n_bins)
            else:
                f, bh, w = args
                ls, lw = _exact_leaf_stats(binned, f[None], bh[None], stats,
                                           w, depth, n_bins)
            return (_class_leaf(ls[0], lw[0]) if task == "classification"
                    else _mean_leaf(ls[0], lw[0])[:, None])

        leaf = jax.lax.map(
            leaf_one, ((feat, bheap, bases, weights) if deep
                       else (feat, bheap, weights)))
    if deep:
        return {"feat_lv": feat, "thresh_lv": thr, "bins_lv": bheap,
                "base_lv": bases, "leaf": leaf, "edges": edges}
    return {"feat": feat, "thresh": thr, "bins": bheap, "leaf": leaf,
            "edges": edges}


def _rf_tree_keys(seed, t):
    """(bootstrap key, feature-subset key) of tree ``t`` of the forest
    configuration seeded ``seed``."""
    base = jax.random.PRNGKey(seed.astype(jnp.uint32))
    return jax.random.split(jax.random.fold_in(base, t))


def _rf_p_feat(d: int, task: str) -> float:
    """Share of the columns a tree draws (Spark featureSubsetStrategy auto:
    sqrt for classification, 1/3 for regression)."""
    return (float(np.ceil(np.sqrt(d)) / d) if task == "classification"
            else max(1.0 / 3.0, 1.0 / d))


def _rf_seeds(B: int) -> np.ndarray:
    """The forest family's per-lane seeds: host constants, like maxDepth."""
    return np.arange(B, dtype=np.float32) + 7.0


@lru_cache(maxsize=64)
def _rf_feature_table(B: int, n_trees: int, d: int, p_feat: float):
    """Every tree's drawn columns for a forest fit of B lanes x n_trees:
    (fmasks (B, n_trees, d) bool, feat_idx (B, n_trees, d_sub) int32 or
    None), numpy, drawn ONCE on the host before any trace.

    The subsets are a pure function of (seed, tree, d, p_feat) and the
    seeds are `_rf_seeds`, so the width of the widest subset is known
    before the growers are traced: d_sub is that width rounded up to a
    multiple of 4 (d_sub·32 bin lanes a multiple of 128), an exact bound
    and not a tail estimate; no tree's column is ever dropped. ``feat_idx``
    lists each tree's columns ascending, padded with the sentinel d; it is
    None where d_sub >= d, and the growers then run full width. Masks and
    index come from this one table: there is no second draw to disagree."""
    with jax.ensure_compile_time_eval():
        draw = jax.vmap(lambda seed: jax.vmap(
            lambda t: jax.random.bernoulli(
                _rf_tree_keys(seed, t)[1], p_feat, (d,))
        )(jnp.arange(n_trees)))
        fmasks = np.asarray(draw(jnp.asarray(_rf_seeds(B))))
    d_sub = max(4, -(-int(fmasks.sum(-1).max()) // 4) * 4)
    if d_sub >= d:
        return fmasks, None
    cols = np.where(fmasks, np.arange(d, dtype=np.int32), np.int32(d))
    return fmasks, np.sort(cols, axis=-1)[..., :d_sub]


def _rf_config_chunk(B: int, S: int, n_trees: int, depth: int, n_slots: int,
                     k: int, d: int, n_bins: int, d_sub: int = 0) -> int:
    """Configurations per chunk of ``_fit_rf_batch``'s ``lax.map``.
    ``d_sub``: the per-tree column width where the growers run compact
    (`_rf_feature_table`), 0 where they run all d columns."""
    deep = n_slots > 0
    dw = d_sub or d                      # columns a tree's histogram holds
    # chunk budget covers BOTH the grower's bf16 (S, Tb·nodes) transients
    # and the sweep leaf-stat path's f32 (S, k+1, Tb) A_cols tensor (f32
    # counts double in the bf16-element budget); the capped grower's level
    # width is n_slots·k (no sibling subtraction, k stat planes per slot)
    lane_w = (min(2 ** (depth - 1), n_slots) * k if deep
              else 2 ** (depth - 1))
    cb = max(1, min(B, _CFG_CHUNK_ELEMS
                    // (S * n_trees * max(lane_w, 2 * (k + 1)))))
    # ...AND the per-level histogram/gain pipeline, whose (Tb·nodes, d,
    # n_bins, k) f32 tensors scale with the FEATURE count, not the sample:
    # at small S the first bound lets whole wide grids through, and a
    # 600-column text-hashed vector at depth 12 then asks for >25 GB of
    # HBM (seen on the Titanic pipeline; XLA holds several of these
    # alive across the cumsum/gain chain)
    nodes_w = min(2 ** depth, n_slots) if deep else 2 ** (depth - 1)
    cb = max(1, min(cb, _LEVEL_HIST_ELEMS
                    // (n_trees * nodes_w * dw * n_bins * k)))
    if d_sub:
        # ...AND the compact path's bin one-hot, which every tree has of
        # its own: (S, trees, d_sub·n_bins) bf16
        cb = max(1, min(cb, _CFG_CHUNK_ELEMS
                        // (S * n_trees * d_sub * n_bins)))
    if k > 2:
        # ...AND, with more than two statistic planes (a many-class label),
        # the flat histogram's row-blocked contraction: past the Pallas
        # kernel's 1024 stat columns the engine keeps one f32 partial of
        # (trees·nodes·k, d·n_bins) per row block alive at once. At 23
        # planes, 9 depth-6 configurations a chunk asked the chip's compiler
        # for 19.2 GB (6.8 GB of it this one tensor) and the sweep fell down
        # the exhaustion ladder (PR 26). Two planes never bind here.
        cb = max(1, min(cb, _HIST_PARTIAL_ELEMS
                        // (_hist_shards() * n_trees * nodes_w * dw * n_bins
                            * k)))
    # of the sizes the budgets allow (down to half the largest), the one
    # that grows the fewest padded configurations, the larger on a tie:
    # 18 lanes in chunks of 5 would grow 20, in chunks of 3 they grow 18
    return min(range(cb, cb // 2, -1), key=lambda c: -(-B // c) * c)


@partial(jax.jit, static_argnames=("depth", "n_bins", "num_classes", "task",
                                   "n_trees", "sweep", "n_slots"))
def _fit_rf_batch(X, y, weights, max_depth, min_inst, min_gain, num_trees,
                  subsample, seeds, fmasks, feat_idx=None, *, depth, n_bins,
                  num_classes, task, n_trees, sweep=False, n_slots=0):
    """``fmasks`` (B, n_trees, d) and ``feat_idx`` (B, n_trees, d_sub) or
    None: the per-tree feature subsets of `_rf_feature_table`, one draw per
    tree for all of its levels."""
    n, d = X.shape
    samp, edges, binned, binned_s, stats, mode, w_scale = \
        _prep_tree_inputs(X, y, n_bins, num_classes, task,
                          full_bin=not sweep, sweep=sweep)
    S = binned_s.shape[0]
    k = stats.shape[1]
    stats_s = stats[samp]
    deep = n_slots > 0
    L = min(2 ** depth, n_slots) if deep else 2 ** depth
    B = weights.shape[0]
    cb = _rf_config_chunk(B, S, n_trees, depth, n_slots, k, d, n_bins,
                          0 if feat_idx is None else feat_idx.shape[-1])

    def one_chunk(w_c, md, mi, mg, ss, seed, fmask_c, fidx_c):
        """Grow a chunk of cb configs — cb·n_trees trees — in one
        tree-batched forest call. Leading axes here are (cb,)."""
        Tb = cb * n_trees
        w_s = w_c[:, samp] * w_scale                        # (cb, S)
        fidx = None if fidx_c is None else fidx_c.reshape(Tb, -1)

        def boots_one(seed_c, ss_c):
            # Poisson(ss) bootstrap weights by inverse-CDF over uniforms,
            # truncated at 7 (P[X>7 | lam<=1] < 1e-6) — 3x cheaper than
            # jax.random.poisson's rejection sampling at these volumes
            ks = jnp.arange(8, dtype=jnp.float32)
            lam = jnp.maximum(ss_c.astype(jnp.float32), 1e-12)
            log_pmf = (-lam + ks * jnp.log(lam)
                       - jax.scipy.special.gammaln(ks + 1.0))
            cdf = jnp.cumsum(jnp.exp(log_pmf))

            def per_tree(t):
                u = jax.random.uniform(_rf_tree_keys(seed_c, t)[0], (S,))
                return (u[:, None] > cdf[None, :]).sum(-1).astype(X.dtype)

            return jax.vmap(per_tree)(jnp.arange(n_trees))

        boots = jax.vmap(boots_one)(seed, ss)               # (cb, T, S)
        # per-tree row weight = config fold weight x bootstrap; flatten the
        # (config, tree) axes into the lane dim: t-major lane = c*T + t
        w_ts = (w_s[:, None, :] * boots).reshape(Tb, S).T   # (S, Tb)
        sw_list = [stats_s[:, k_i][:, None] * w_ts for k_i in range(k)]
        cfg = {"max_depth": jnp.repeat(md, n_trees),
               "min_instances": jnp.repeat(mi, n_trees),
               "min_info_gain": jnp.repeat(mg, n_trees),
               "lam": jnp.full((Tb,), 1e-6, jnp.float32),
               "min_child_weight": jnp.zeros((Tb,), jnp.float32)}
        if deep:
            fs, ths, bhs, abs_, node_s = _grow_forest_capped(
                binned_s, edges, sw_list, fmask_c.reshape(Tb, d), cfg,
                depth=depth, n_bins=n_bins, mode=mode, n_slots=n_slots,
                feat_idx=fidx)
        else:
            fs, ths, bhs, node_s = _grow_forest(
                binned_s, edges, sw_list, fmask_c.reshape(Tb, d), cfg,
                depth=depth, n_bins=n_bins, mode=mode, feat_idx=fidx)
            abs_ = jnp.zeros((Tb, 0), jnp.int32)

        if sweep:
            # sample leaf stats for the WHOLE chunk in one blocked
            # segment-sum: per-tree stat columns A[s, j, t] = stat_j(s) ·
            # w_{config(t)}(s), reduced by _diag_leaf_hist's 64-tree blocks
            # — replaces cb separate per-config histogram dispatches
            # (~100ms/chunk of launch overhead at cb=20)
            w_ts = jnp.repeat(w_s, n_trees, axis=0).T        # (S, Tb)
            stats_aug = jnp.concatenate(
                [stats_s, jnp.ones((S, 1), stats_s.dtype)], axis=1)
            A_cols = stats_aug[:, :, None] * w_ts[:, None, :]  # (S, k+1, Tb)
            sums = _diag_leaf_hist(node_s, A_cols.astype(jnp.float32), L)
            sums = sums.transpose(1, 2, 0)                   # (Tb, L, k+1)
            ls, lw = sums[..., :-1], sums[..., -1]
            leaf_flat = (jax.vmap(_class_leaf)(ls, lw)
                         if task == "classification"
                         else jax.vmap(_mean_leaf)(ls, lw)[:, :, None])
            leaf_c = leaf_flat.reshape((cb, n_trees) + leaf_flat.shape[1:])
        else:
            leaf_c = jnp.zeros(
                (cb, n_trees, L, k if task == "classification" else 1),
                jnp.float32)
        tail = fs.shape[1:]
        return (fs.reshape((cb, n_trees) + tail),
                ths.reshape((cb, n_trees) + tail),
                bhs.reshape((cb, n_trees) + tail),
                abs_.reshape((cb, n_trees) + abs_.shape[1:]), leaf_c)

    n_chunks = -(-B // cb)
    B_pad = n_chunks * cb
    args = (weights, max_depth, min_inst, min_gain, subsample, seeds,
            fmasks, feat_idx)
    if B_pad != B:
        idx = jnp.arange(B_pad) % B
        args = jax.tree_util.tree_map(lambda a: a[idx], args)
    args = jax.tree_util.tree_map(
        lambda a: a.reshape((n_chunks, cb) + a.shape[1:]), args)
    feat, thr, bheap, bases, leaf = jax.lax.map(
        lambda ch: one_chunk(*ch), args)
    feat, thr, bheap, bases, leaf = jax.tree_util.tree_map(
        lambda a: a.reshape((B_pad,) + a.shape[2:])[:B],
        (feat, thr, bheap, bases, leaf))

    if not sweep:
        # EXACT full-data leaf stats per config (fused descent kernel is a
        # pallas call — sequential per config, outside the batched grower)
        def leaf_one(args):
            if deep:
                f, bh, ab, w = args
                ls, lw = _exact_leaf_stats_chain(binned, f, bh, ab, stats,
                                                 w, n_bins)
            else:
                f, bh, w = args
                ls, lw = _exact_leaf_stats(binned, f, bh, stats, w, depth,
                                           n_bins)
            return (jax.vmap(_class_leaf)(ls, lw)
                    if task == "classification"
                    else jax.vmap(_mean_leaf)(ls, lw)[:, :, None])

        leaf = jax.lax.map(
            leaf_one, ((feat, bheap, bases, weights) if deep
                       else (feat, bheap, weights)))
    tree_mask = (jnp.arange(n_trees)[None, :] <
                 num_trees[:, None]).astype(jnp.float32)
    if deep:
        return {"feat_lv": feat, "thresh_lv": thr, "bins_lv": bheap,
                "base_lv": bases, "leaf": leaf, "tree_mask": tree_mask,
                "edges": edges}
    return {"feat": feat, "thresh": thr, "bins": bheap, "leaf": leaf,
            "tree_mask": tree_mask,
            "edges": edges}


@partial(jax.jit, static_argnames=("depth", "n_bins", "num_classes", "task",
                                   "n_rounds", "sweep", "n_slots"))
def _fit_gbt_batch(X, y, weights, max_depth, min_inst, min_gain, max_iter,
                   step_size, lam, min_child_weight, *, depth, n_bins,
                   num_classes, task, n_rounds, sweep=False, n_slots=0):
    """Gradient boosting: binary logistic / regression squared / multiclass
    softmax. Each round grows ONE tree-batched forest over all configs ×
    classes (`_grow_forest`) — the per-round hist/route ops are Tb-wide
    instead of |configs| narrow vmapped copies."""
    n, d = X.shape
    samp, edges, _, binned_s, _, _, w_scale = \
        _prep_tree_inputs(X, y, n_bins, num_classes, "regression",
                          full_bin=False, sweep=sweep)
    C = num_classes if task == "multiclass" else 1
    B = weights.shape[0]
    S = binned_s.shape[0]
    deep = n_slots > 0
    L = min(2 ** depth, n_slots) if deep else 2 ** depth
    Tb = B * C                                             # trees per round
    y_s = y[samp]
    Y1_s = (jax.nn.one_hot(y_s.astype(jnp.int32), max(C, 2), dtype=X.dtype)
            if task == "multiclass" else None)
    W_s = weights[:, samp] * w_scale                       # (B, S)
    # per-tree (config, class) row weights / cfg: lane order t = b*C + c
    w_tb = jnp.repeat(W_s, C, axis=0).T                    # (S, Tb)
    rep = lambda v: jnp.repeat(v, C)                       # (B,) -> (Tb,)
    cfg = {"max_depth": rep(max_depth), "min_instances": rep(min_inst),
           "min_info_gain": rep(min_gain), "lam": rep(lam),
           "min_child_weight": rep(min_child_weight)}
    lam_t = rep(lam)
    fmasks = jnp.ones((Tb, d), bool)
    # boosting state lives on the split-search sample: gradients, F and leaf
    # values all come from it (the XGBoost subsample design point); at 65k
    # rows and ≥2^depth≥8 leaves every leaf still averages 1000+ rows
    if task == "regression":
        # pinned row sums: f0 must stay bit-identical when rows shard over
        # the mesh 'data' axis (docs/trees.md, "Determinism")
        f0 = (pinned_row_sum(weights * y[None, :], axis=1)
              / jnp.maximum(pinned_row_sum(weights, axis=1), 1.0))[:, None]
    else:
        f0 = jnp.zeros((B, C), X.dtype)
    F_init = jnp.broadcast_to(f0[:, :, None], (B, C, S))

    def round_step(F, t):                                   # F: (B, C, S)
        if task == "binary":
            p = jax.nn.sigmoid(F[:, 0, :])                  # (B, S)
            g = (p - y_s[None, :])[:, None, :]
            h = jnp.maximum(p * (1 - p), 1e-6)[:, None, :]
        elif task == "regression":
            g = F - y_s[None, None, :]
            h = jnp.ones_like(g)
        else:
            P = jax.nn.softmax(F, axis=1)                   # (B, C, S)
            g = P - Y1_s.T[None, :C, :]
            h = jnp.maximum(P * (1 - P), 1e-6)
        g_tb = g.reshape(Tb, S).T                           # (S, Tb)
        h_tb = h.reshape(Tb, S).T
        sw_list = [(g_tb * w_tb), (h_tb * w_tb), w_tb]
        if deep:
            # slot-chain trees (maxDepth > heap practical limit): leaves
            # via the f32-exact per-tree segment sum — the last-level
            # histogram trick below does not apply (leaves settle at many
            # levels), and the f32 path needs no bf16 noise clamp
            fs, ths, bhs, abs_, node_s = _grow_forest_capped(
                binned_s, edges, sw_list, fmasks, cfg,
                depth=depth, n_bins=n_bins, mode="gh", n_slots=n_slots)
            gh = _diag_leaf_hist(
                node_s, jnp.stack([g_tb * w_tb, h_tb * w_tb], axis=1
                                  ).astype(jnp.float32), L)  # (2, Tb, L)
            leaf = -gh[0] / (gh[1] + lam_t[:, None] + 1e-12)  # (Tb, L)
        elif sweep:
            # CV candidates take Newton leaves straight off the final
            # level's histogram (bf16-summed, free); the refit winner
            # (sweep=False) keeps the exact f32 segment-sum below since
            # its leaves are SERVED predictions
            fs, ths, bhs, node_s, lst = _grow_forest(
                binned_s, edges, sw_list, fmasks, cfg,
                depth=depth, n_bins=n_bins, mode="gh",
                return_leaf_stats=True)
            abs_ = jnp.zeros((Tb, 0), jnp.int32)
            # bf16 sibling-subtracted histograms leave cancellation noise in
            # near-empty leaves' H; with small lam -G/H can then be huge and
            # wrong-signed, polluting later boosting rounds. The subtraction
            # error is ~eps_bf16·(parent H), so zero a leaf only when its H
            # is below that PARENT-relative floor (parent = leaf + heap
            # sibling) — a legitimately small leaf under a small parent
            # (min_child_weight territory) stays alive, unlike a
            # root-relative cutoff which would override the grid's
            # minChildWeight for deep trees
            h_leaf = lst[..., 1]                              # (Tb, L)
            L_ = h_leaf.shape[-1]
            if L_ >= 2:
                h_sib = h_leaf.reshape(-1, L_ // 2, 2)[..., ::-1].reshape(
                    h_leaf.shape)
                h_parent = h_leaf + h_sib
            else:
                h_parent = h_leaf
            raw = -lst[..., 0] / (h_leaf + lam_t[:, None] + 1e-12)
            leaf = jnp.where(h_leaf < 2 ** -8 * h_parent,
                             jnp.zeros_like(raw), raw)        # (Tb, L)
        else:
            fs, ths, bhs, node_s = _grow_forest(
                binned_s, edges, sw_list, fmasks, cfg,
                depth=depth, n_bins=n_bins, mode="gh")
            abs_ = jnp.zeros((Tb, 0), jnp.int32)
            # Newton leaves from per-tree G/H segment sums (f32 exact),
            # both stats reduced in one histogram call
            gh = _diag_leaf_hist(
                node_s, jnp.stack([g_tb * w_tb, h_tb * w_tb], axis=1
                                  ).astype(jnp.float32), L)  # (2, Tb, L)
            leaf = -gh[0] / (gh[1] + lam_t[:, None] + 1e-12)  # (Tb, L)
        # per-row leaf values via one-hot einsum — a (Tb, S) take_along_axis
        # gather measures ~3x slower on TPU; HIGHEST keeps the Newton values
        # exact in the boosting state. Chunk the tree axis so the (S, tb, L)
        # one-hot operand stays bounded (large multiclass sweeps would OOM
        # materializing all Tb*L columns at once)
        tb_chunk = max(1, 16384 // L)
        preds = []
        for lo in range(0, Tb, tb_chunk):
            hi2 = min(lo + tb_chunk, Tb)
            l_oh = (node_s[:, lo:hi2, None]
                    == jnp.arange(L, dtype=jnp.int32)).astype(jnp.float32)
            preds.append(jnp.einsum(
                "stl,tl->ts", l_oh, leaf[lo:hi2],
                precision=jax.lax.Precision.HIGHEST))
        pred = jnp.concatenate(preds, axis=0) if len(preds) > 1 \
            else preds[0]                                       # (Tb, S)
        active = rep((t.astype(jnp.float32) < max_iter).astype(X.dtype))
        eta_t = rep(step_size)
        scale = (eta_t * active).reshape(B, C)[:, :, None]
        F_new = F + scale * pred.reshape(B, C, S)
        return F_new, (fs, ths, bhs, abs_, leaf)

    _, (feat, thr, bheap, bases, leaf) = jax.lax.scan(
        round_step, F_init, jnp.arange(n_rounds))

    # (rounds, Tb=B*C, ...) → (B, rounds, C, ...)
    def to_bc(a):
        return jnp.swapaxes(
            a.reshape((n_rounds, B, C) + a.shape[2:]), 0, 1)

    feat, thr, bheap, bases, leaf = map(
        to_bc, (feat, thr, bheap, bases, leaf))
    tree_mask = (jnp.arange(n_rounds)[None, :] <
                 max_iter[:, None]).astype(jnp.float32)
    if deep:
        return {"feat_lv": feat, "thresh_lv": thr, "bins_lv": bheap,
                "base_lv": bases, "leaf": leaf, "f0": f0, "eta": step_size,
                "tree_mask": tree_mask, "edges": edges}
    return {"feat": feat, "thresh": thr, "bins": bheap, "leaf": leaf,
            "f0": f0, "eta": step_size, "tree_mask": tree_mask,
            "edges": edges}


# ---------------------------------------------------------------------------
# Batched predict drivers
# ---------------------------------------------------------------------------

def _forest_values(codes, feat_heaps, bin_heaps, leaf, *, depth, n_bins):
    """Σ_t leaf[t, node(row, t), :] via the fused descent kernel, chunking
    the tree axis at the kernel's cap. leaf: (T, L, k) with any per-tree
    weighting baked in."""
    T = feat_heaps.shape[0]
    out = None
    for lo in range(0, T, _PREDICT_TREE_CHUNK):
        hi = min(lo + _PREDICT_TREE_CHUNK, T)
        part = forest_predict(codes, feat_heaps[lo:hi], bin_heaps[lo:hi],
                              leaf[lo:hi], depth=depth, n_bins=n_bins)
        out = part if out is None else out + part
    return out


def _forest_values_grouped(codes, feat, bins, leaf, *, depth, n_bins):
    """Per-config leaf-value sums for a BATCH of configs in shared descent
    calls: a group's trees are concatenated and each config's leaf values
    occupy their own block of output columns, so one kernel pass scores the
    whole group (36 per-config launches → a handful; the summation over a
    config's trees stays inside the kernel's final matmul because other
    configs' columns are zero). feat/bins: (B, T, H); leaf: (B, T, L, k)
    with per-tree weighting baked in. Returns (B, n, k)."""
    B, T, H = feat.shape
    L, k = leaf.shape[2], leaf.shape[3]
    n = codes.shape[0]
    g = max(1, min(B, 128 // max(k, 1)))   # ≤128 output columns per call
    outs = []
    for lo in range(0, B, g):
        hi = min(lo + g, B)
        gb = hi - lo
        f_all = feat[lo:hi].reshape(gb * T, H)
        b_all = bins[lo:hi].reshape(gb * T, H)
        blocks = [jnp.pad(leaf[lo + c],
                          ((0, 0), (0, 0), (c * k, (gb - 1 - c) * k)))
                  for c in range(gb)]
        lf = jnp.concatenate(blocks, axis=0)            # (gb*T, L, gb*k)
        vals = _forest_values(codes, f_all, b_all, lf,
                              depth=depth, n_bins=n_bins)  # (n, gb*k)
        outs.append(vals.reshape(n, gb, k))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.transpose(1, 0, 2)                       # (B, n, k)


@partial(jax.jit, static_argnames=("depth", "n_bins"))
def _predict_dt_batch(feat, bins, leaf, edges, X, *, depth, n_bins):
    codes = _bin_features(X, edges)
    return _forest_values_grouped(codes, feat[:, None], bins[:, None],
                                  leaf[:, None], depth=depth,
                                  n_bins=n_bins)           # (B, n, k)


@partial(jax.jit, static_argnames=("depth", "n_bins"))
def _predict_rf_batch(feat, bins, leaf, tree_mask, edges, X, *, depth,
                      n_bins):
    codes = _bin_features(X, edges)
    lw = leaf * tree_mask[:, :, None, None]                # (B, T, L, k)
    out = _forest_values_grouped(codes, feat, bins, lw,
                                 depth=depth, n_bins=n_bins)
    return out / jnp.maximum(tree_mask.sum(1), 1.0)[:, None, None]


@partial(jax.jit, static_argnames=("depth", "n_bins"))
def _predict_gbt_batch(feat, bins, leaf, f0, eta, tree_mask, edges, X, *,
                       depth, n_bins):
    codes = _bin_features(X, edges)
    B, T, C, H = feat.shape
    L = leaf.shape[-1]
    # class-routing leaf table: value·one-hot(class) per (tree·class, leaf)
    # so one descent over T·C trees yields per-class margins
    lv = leaf * tree_mask[:, :, None, None]                # (B, T, C, L)
    cls_oh = (jnp.arange(C)[:, None]
              == jnp.arange(C)[None, :]).astype(lv.dtype)  # (C, C)
    M = lv[:, :, :, :, None] * cls_oh[None, None, :, None, :]
    contrib = _forest_values_grouped(
        codes, feat.reshape(B, T * C, H), bins.reshape(B, T * C, H),
        M.reshape(B, T * C, L, C), depth=depth, n_bins=n_bins)  # (B, n, C)
    return (f0[:, None, :] + eta[:, None, None] * contrib
            ).transpose(0, 2, 1)                           # (B, C, n)


# -- slot-chain predict drivers ---------------------------------------------

def _forest_values_grouped_chain(codes, feat, bins, bases, leaf, *, n_bins):
    """Chain-format analog of `_forest_values_grouped`: per-config leaf-value
    sums for a batch of slot-chain configs in shared descent calls.
    feat/bins/bases: (B, T, depth, W); leaf: (B, T, W_out, k)."""
    B, T, depth, W = feat.shape
    W_out, k = leaf.shape[2], leaf.shape[3]
    n = codes.shape[0]
    g = max(1, min(B, 128 // max(k, 1)))
    outs = []
    for lo in range(0, B, g):
        hi = min(lo + g, B)
        gb = hi - lo
        f_all = feat[lo:hi].reshape(gb * T, depth, W)
        b_all = bins[lo:hi].reshape(gb * T, depth, W)
        a_all = bases[lo:hi].reshape(gb * T, depth, W)
        blocks = [jnp.pad(leaf[lo + c],
                          ((0, 0), (0, 0), (c * k, (gb - 1 - c) * k)))
                  for c in range(gb)]
        lf = jnp.concatenate(blocks, axis=0)            # (gb*T, W_out, gb*k)
        vals = forest_predict_chain(codes, f_all, b_all, a_all, lf,
                                    n_bins=n_bins)      # (n, gb*k)
        outs.append(vals.reshape(n, gb, k))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.transpose(1, 0, 2)                       # (B, n, k)


@partial(jax.jit, static_argnames=("n_bins",))
def _predict_dt_chain_batch(feat, bins, bases, leaf, edges, X, *, n_bins):
    codes = _bin_features(X, edges)
    return _forest_values_grouped_chain(
        codes, feat[:, None], bins[:, None], bases[:, None], leaf[:, None],
        n_bins=n_bins)                                      # (B, n, k)


@partial(jax.jit, static_argnames=("n_bins",))
def _predict_rf_chain_batch(feat, bins, bases, leaf, tree_mask, edges, X, *,
                            n_bins):
    codes = _bin_features(X, edges)
    lw = leaf * tree_mask[:, :, None, None]                # (B, T, W_out, k)
    out = _forest_values_grouped_chain(codes, feat, bins, bases, lw,
                                       n_bins=n_bins)
    return out / jnp.maximum(tree_mask.sum(1), 1.0)[:, None, None]


@partial(jax.jit, static_argnames=("n_bins",))
def _predict_gbt_chain_batch(feat, bins, bases, leaf, f0, eta, tree_mask,
                             edges, X, *, n_bins):
    codes = _bin_features(X, edges)
    B, T, C, depth, W = feat.shape
    W_out = leaf.shape[-1]
    lv = leaf * tree_mask[:, :, None, None]                # (B, T, C, W_out)
    cls_oh = (jnp.arange(C)[:, None]
              == jnp.arange(C)[None, :]).astype(lv.dtype)  # (C, C)
    M = lv[:, :, :, :, None] * cls_oh[None, None, :, None, :]
    contrib = _forest_values_grouped_chain(
        codes, feat.reshape(B, T * C, depth, W),
        bins.reshape(B, T * C, depth, W),
        bases.reshape(B, T * C, depth, W),
        M.reshape(B, T * C, W_out, C), n_bins=n_bins)      # (B, n, C)
    return (f0[:, None, :] + eta[:, None, None] * contrib
            ).transpose(0, 2, 1)                           # (B, C, n)


# ---------------------------------------------------------------------------
# Model families
# ---------------------------------------------------------------------------

def _g(grid, key, default):
    return grid[key] if key in grid else jnp.full_like(
        next(iter(grid.values())), default)


class _TreeFamilyBase(ModelFamily):
    #: +inf thresholds are the "stopped node routes left" sentinel in both
    #: the heap (thresh) and slot-chain (thresh_lv) layouts — legitimate
    #: fitted state, exempted from the refit finite-params guard
    inf_ok_params = ("thresh", "thresh_lv")
    #: config sweep runs under chunked lax.map (sequential per chip), so the
    #: batch axis cannot shard over the 'model' mesh axis; rows still shard.
    shardable = False
    #: histogram builds route through the engine's pinned contraction —
    #: the fused sweep dispatcher arms the ``hist.build`` chaos gate and
    #: the engine mesh context for these families
    uses_hist_engine = True

    def sweep_fit_batch(self, X, y, weights, grid, num_classes):
        """CV-sweep fits: leaf values come from the split-search sample —
        the sweep only scores validation rows with them, and the winner is
        refit via plain ``fit_batch`` with EXACT full-data leaves (reference
        ModelSelector.fit refits best on full prepared train :158-159)."""
        return self.fit_batch(X, y, weights, grid, num_classes, sweep=True)

    task_of = staticmethod(lambda problem: "classification"
                           if problem in ("binary", "multiclass")
                           else "regression")

    def _task(self, num_classes):
        if "regression" in self.supports and len(self.supports) == 1:
            return "regression"
        return "classification"

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        """``histShards``: K, the pinned row blocks of the histogram
        engine's contraction; ``combine``: the spelling `_tree_combine`
        traces for their partials (``"fused"`` on one device, ``"halving"``
        under an engine mesh: call this under the context the fit is
        traced in); ``sampleRows``: the rows the growers' histograms are
        built from (`_sample_rows`), all a sweep fit reads of the table."""
        return {"histShards": _hist_shards(), "combine": _combine_form(),
                "sampleRows": min(rows, _SWEEP_HIST_SAMPLE if sweep
                                  else _HIST_SAMPLE)}

    def predict_span_attrs(self, fitted, rows):
        """``trees`` descended a row (a boosted fit's rounds x class
        planes), their ``depth``, the ``features`` a row has, and
        ``treeChunks``: the descent kernel's calls a predict
        (`forest_predict_chain` takes `_T_CHAIN` trees a call,
        `_forest_values` `_PREDICT_TREE_CHUNK`); and the block the kernel walks
        the ``rows`` of the matrix handed to the predict in:
        ``blockRows`` a grid step and ``laneChunk``, the lanes of its widest
        select product (`ops.forest.chain_block_shape`: 64 rows where the
        call is under one wide block; a heap's kernel takes `_BLK_R` rows
        and a level's lanes whole, 0)."""
        p = fitted.params
        chain = "base_lv" in p
        shape = np.shape(p["feat_lv"] if chain else p["feat"])
        trees = int(np.prod(shape[:-2] if chain else shape[:-1]))
        depth = int(shape[-2]) if chain else _depth_of(shape[-1] + 1)
        block, chunk = (chain_block_shape(int(rows), depth, int(shape[-1]))
                        if chain else (_BLK_R, 0))
        return {"trees": trees, "features": int(np.shape(p["edges"])[-2]),
                "depth": depth,
                "treeChunks": -(-trees // (_T_CHAIN if chain
                                           else _PREDICT_TREE_CHUNK)),
                "blockRows": block, "laneChunk": chunk}

    def select_params(self, batched, idx):
        """Per-config slice, except the bin-edge table, which is shared by
        every configuration of a fit and stored once."""
        import jax
        return {k: (np.asarray(v) if k == "edges" else np.asarray(v[idx]))
                for k, v in batched.items()}

    def slice_params(self, batched, lo, hi):
        # quantile bin edges are shared across the whole sweep
        return {k: (v if k == "edges" else v[lo:hi])
                for k, v in batched.items()}

    @staticmethod
    def _edges_of(params):
        """Shared (d, n_bins−1) edge table whether params came from a batched
        fit (2-D) or went through predict_one's uniform [None] stacking."""
        e = jnp.asarray(params["edges"])
        return e[0] if e.ndim == 3 else e


#: reference DefaultSelectorParams.MaxDepth {3, 6, 12}
#: (DefaultSelectorParams.scala:37). Depths ≤ _MAX_HEAP_DEPTH grow/serve as
#: complete heaps; deeper ones as slot-chain ("leaf budget") trees.
_DEPTHS = (3, 6, 12)

#: beyond this depth a complete heap's 2^depth layout outgrows HBM/VMEM and
#: the slot-chain representation takes over
_MAX_HEAP_DEPTH = 8

#: slot-chain leaf budgets: CV-sweep candidates rank configs (LightGBM-scale
#: num_leaves suffices — the winner is regrown exactly), served refits get
#: the full budget
_SWEEP_SLOTS = 64
_REFIT_SLOTS = 256


def _heap_to_chain(params, d_heap: int, depth: int, W: int, n_bins: int,
                   leaf_axis: int):
    """EXACT re-expression of complete-heap trees in the slot-chain layout.

    A heap node j at level l maps to chain slot j with child base 2j (the
    positional child rule); levels past the heap's depth are identity
    carries (sentinel bin ⇒ go 0, base = slot), so a row reaching heap leaf
    j stays at slot j through the remaining levels. Requires 2^d_heap ≤ W.
    Non-tree entries (edges, tree_mask, f0, eta) pass through."""
    if 2 ** d_heap > W:
        raise ValueError(f"heap depth {d_heap} needs {2 ** d_heap} slots, "
                         f"chain budget is {W}")
    feat, bins = params["feat"], params["bins"]
    thr, leaf = params["thresh"], params["leaf"]
    lead = feat.shape[:-1]
    W_out = min(2 ** depth, W)
    f_lv = jnp.zeros(lead + (depth, W), jnp.int32)
    b_lv = jnp.full(lead + (depth, W), n_bins, jnp.int32)
    t_lv = jnp.full(lead + (depth, W), jnp.inf, jnp.float32)
    a_lv = jnp.zeros(lead + (depth, W), jnp.int32)
    for level in range(depth):
        Wl = min(2 ** level, W)
        if level < d_heap:
            base_i, m = 2 ** level - 1, 2 ** level
            f_lv = f_lv.at[..., level, :m].set(feat[..., base_i:base_i + m])
            b_lv = b_lv.at[..., level, :m].set(bins[..., base_i:base_i + m])
            t_lv = t_lv.at[..., level, :m].set(thr[..., base_i:base_i + m])
            a_lv = a_lv.at[..., level, :m].set(
                2 * jnp.arange(m, dtype=jnp.int32))
        else:
            a_lv = a_lv.at[..., level, :Wl].set(
                jnp.arange(Wl, dtype=jnp.int32))
    ax = leaf_axis % leaf.ndim
    pad = [(0, 0)] * leaf.ndim
    pad[ax] = (0, W_out - leaf.shape[ax])
    out = {k: v for k, v in params.items()
           if k not in ("feat", "bins", "thresh", "leaf")}
    out.update({"feat_lv": f_lv, "bins_lv": b_lv, "thresh_lv": t_lv,
                "base_lv": a_lv, "leaf": jnp.pad(leaf, pad)})
    return out


def _pad_chain_depth(params, d_small: int, depth: int, n_bins: int,
                     leaf_axis: int):
    """Extend chain tables from d_small to depth levels with identity
    carries, and pad the leaf axis to the deeper W_out. Exact."""
    if d_small == depth:
        return params
    f_lv = params["feat_lv"]
    lead, W = f_lv.shape[:-2], f_lv.shape[-1]
    W_out = min(2 ** depth, W)
    ext = depth - d_small
    out = dict(params)

    def pad_levels(a, fill):
        pad = [(0, 0)] * (a.ndim - 2) + [(0, ext), (0, 0)]
        return jnp.pad(a, pad, constant_values=fill)

    out["feat_lv"] = pad_levels(f_lv, 0)
    out["bins_lv"] = pad_levels(params["bins_lv"], n_bins)
    out["thresh_lv"] = pad_levels(params["thresh_lv"], jnp.inf)
    a_lv = pad_levels(params["base_lv"], 0)
    for level in range(d_small, depth):
        Wl = min(2 ** level, W)
        a_lv = a_lv.at[..., level, :Wl].set(jnp.arange(Wl, dtype=jnp.int32))
    out["base_lv"] = a_lv
    leaf = params["leaf"]
    ax = leaf_axis % leaf.ndim
    pad = [(0, 0)] * leaf.ndim
    pad[ax] = (0, W_out - leaf.shape[ax])
    out["leaf"] = jnp.pad(leaf, pad)
    return out


def _embed_depth(params, d_small: int, d_max: int, n_bins: int,
                 leaf_axis: int):
    """Re-express a depth-``d_small`` fit in the depth-``d_max`` layout.

    Complete heaps are level-ordered, so the small heap is a PREFIX of the
    big one (remaining nodes: sentinel ⇒ route left), and a row at small
    leaf l descends all-left to big leaf l·(L_max/L_small) — the embedding
    is exact, letting mixed-maxDepth grids share one predict program while
    each depth bucket pays only its own growth cost."""
    if d_small == d_max:
        return params
    H_s, H_m = 2 ** d_small - 1, 2 ** d_max - 1
    r = 2 ** (d_max - d_small)
    out = dict(params)

    def pad_last(a, value):
        pad = [(0, 0)] * (a.ndim - 1) + [(0, H_m - H_s)]
        return jnp.pad(a, pad, constant_values=value)

    out["feat"] = pad_last(params["feat"], 0)
    out["thresh"] = pad_last(params["thresh"], jnp.inf)
    out["bins"] = pad_last(params["bins"], n_bins)
    leaf = params["leaf"]
    ax = leaf_axis % leaf.ndim
    shape = list(leaf.shape)
    shape[ax] = shape[ax] * r
    idx = [slice(None)] * leaf.ndim
    idx[ax] = slice(None, None, r)
    out["leaf"] = jnp.zeros(shape, leaf.dtype).at[tuple(idx)].set(leaf)
    return out


def _stitch_parts(B: int, parts):
    """Scatter per-bucket param dicts (config-subset axis 0) back into a
    (B, ...) batch; 'edges' is shared across buckets and passes through."""
    stitched = None
    for idx, p in parts:
        if stitched is None:
            stitched = {k: (v if k == "edges"
                            else jnp.zeros((B,) + v.shape[1:], v.dtype))
                        for k, v in p.items()}
        for k, v in p.items():
            if k != "edges":
                stitched[k] = stitched[k].at[jnp.asarray(idx)].set(v)
    return stitched


def _fit_depth_grouped(grid, weights, fit_group, n_bins: int,
                       leaf_axis: int, fit_group_deep=None, n_slots: int = 0):
    """Partition the config batch by maxDepth and fit each bucket with its
    own (cheap) depth program, embedding results into the deepest layout.
    ``fit_group(sub_grid, sub_weights, depth) -> params``. maxDepth values
    are host-side constants (grid arrays), so grouping is static.

    Depths past ``_MAX_HEAP_DEPTH`` fit via ``fit_group_deep`` (slot-chain
    grower, ``n_slots`` leaf budget); when any bucket is deep, every bucket
    is re-expressed in the chain layout (exact for heaps) so the whole batch
    shares one predict program."""
    md = np.asarray(grid["maxDepth"], dtype=np.float64).reshape(-1)
    uniq = sorted({int(v) for v in md})
    d_max = uniq[-1]
    any_deep = d_max > _MAX_HEAP_DEPTH
    if len(uniq) == 1:
        return (fit_group_deep(grid, weights, d_max, n_slots) if any_deep
                else fit_group(grid, weights, d_max))
    # the shared chain width must hold the DEEPEST heap bucket's full leaf
    # layer (a depth-8 heap has 256 leaves — more than the sweep budget)
    if any_deep:
        d_heap_max = max([u for u in uniq if u <= _MAX_HEAP_DEPTH],
                         default=0)
        n_slots = max(n_slots, 2 ** d_heap_max)
    B = md.shape[0]
    parts = []
    for u in uniq:
        idx = np.nonzero(md == u)[0]
        sub = {k: v[idx] for k, v in grid.items()}
        if u > _MAX_HEAP_DEPTH:
            p = _pad_chain_depth(fit_group_deep(sub, weights[idx], u,
                                                n_slots), u,
                                 d_max, n_bins, leaf_axis)
        elif any_deep:
            p = _heap_to_chain(fit_group(sub, weights[idx], u), u, d_max,
                               n_slots, n_bins, leaf_axis)
        else:
            p = _embed_depth(fit_group(sub, weights[idx], u), u, d_max,
                             n_bins, leaf_axis)
        parts.append((idx, p))
    return _stitch_parts(B, parts)


class DecisionTreeFamilyBase(_TreeFamilyBase):
    """reference OpDecisionTreeClassifier/Regressor (grids per
    DefaultSelectorParams: maxDepth × minInstancesPerNode {10,100}
    × minInfoGain {0.001,0.01,0.1})."""

    def default_grid(self, problem):
        return [{"maxDepth": d, "minInstancesPerNode": mi, "minInfoGain": mg}
                for d in _DEPTHS for mi in (10, 100)
                for mg in (0.001, 0.01, 0.1)]

    def fit_batch(self, X, y, weights, grid, num_classes, sweep=False):
        task = self._task(num_classes)
        n_slots = _SWEEP_SLOTS if sweep else _REFIT_SLOTS

        def fit_group(g, w, depth, slots=0):
            return _fit_dt_batch(
                X, y, w, g["maxDepth"], _g(g, "minInstancesPerNode", 1.0),
                _g(g, "minInfoGain", 0.0),
                depth=depth, n_bins=N_BINS,
                num_classes=max(num_classes, 2), task=task, sweep=sweep,
                n_slots=slots)

        return _fit_depth_grouped(
            grid, weights, fit_group, N_BINS, leaf_axis=-2,
            fit_group_deep=fit_group, n_slots=n_slots)

    def predict_batch(self, params, X, num_classes):
        edges = self._edges_of(params)
        task = self._task(num_classes)
        leaf = params["leaf"]
        if task == "classification" and num_classes <= 2:
            # binary: p0 = 1 − p1, so only the class-1 column needs routing
            # (halves the descent's output columns → 2x configs per call)
            leaf = leaf[..., 1:]
        if "base_lv" in params:
            out = _predict_dt_chain_batch(
                params["feat_lv"], params["bins_lv"], params["base_lv"],
                leaf, edges, X, n_bins=edges.shape[-1] + 1)
        else:
            depth = _depth_of(params["leaf"].shape[-2])
            out = _predict_dt_batch(params["feat"], params["bins"],
                                    leaf, edges, X, depth=depth,
                                    n_bins=edges.shape[-1] + 1)
        if task == "classification" and num_classes <= 2:
            return out[..., 0]
        return _shape_scores(out, num_classes, task)

    def predict_parts(self, fitted: FittedParams, X):
        params = {k: jnp.asarray(v)[None] for k, v in fitted.params.items()}
        out = self.predict_batch(params, X, fitted.num_classes)[0]
        return _parts_j(out, fitted.num_classes, self._task(fitted.num_classes))

    def predict_one(self, fitted: FittedParams, X):
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, jnp.asarray(X)).items()}


class RandomForestFamilyBase(_TreeFamilyBase):
    """reference OpRandomForestClassifier/Regressor (numTrees 50,
    subsample 1.0 per DefaultSelectorParams; bootstrap via Poisson row
    weights, per-tree feature subsets)."""

    def default_grid(self, problem):
        return [{"maxDepth": d, "minInstancesPerNode": mi, "minInfoGain": mg,
                 "numTrees": 50, "subsamplingRate": 1.0}
                for d in _DEPTHS for mi in (10, 100)
                for mg in (0.001, 0.01, 0.1)]

    def fit_batch(self, X, y, weights, grid, num_classes, sweep=False):
        task = self._task(num_classes)
        tree_vals = np.asarray(_g(grid, "numTrees", 20.0))
        n_trees = int(tree_vals.max())
        B = weights.shape[0]
        if sweep:
            # rank with a capped forest; the winner refits at full numTrees
            # (proportional per-config scaling when the grid sweeps
            # numTrees itself — see _sweep_ensemble_cap)
            capped = _sweep_ensemble_cap(tree_vals, _SWEEP_RF_TREES,
                                         "numTrees")
            if capped is not None:
                n_trees = int(capped.max())
                grid = dict(grid, numTrees=jnp.asarray(capped, jnp.float32))
        n_slots = _SWEEP_SLOTS if sweep else _REFIT_SLOTS
        # seeds and the per-tree feature subsets they fix: host constants
        # per lane, grouped by depth with the rest of the grid
        d = X.shape[1]
        fmasks, feat_idx = _rf_feature_table(B, n_trees, d,
                                             _rf_p_feat(d, task))
        grid = dict(grid, _seeds=jnp.asarray(_rf_seeds(B)), _fmasks=fmasks)
        if feat_idx is not None:
            grid["_featIdx"] = feat_idx

        def fit_group(g, w, depth, slots=0):
            return _fit_rf_batch(
                X, y, w, g["maxDepth"],
                _g(g, "minInstancesPerNode", 1.0), _g(g, "minInfoGain", 0.0),
                _g(g, "numTrees", 20.0), _g(g, "subsamplingRate", 1.0),
                g["_seeds"], g["_fmasks"], g.get("_featIdx"),
                depth=depth, n_bins=N_BINS,
                num_classes=max(num_classes, 2), task=task, n_trees=n_trees,
                sweep=sweep, n_slots=slots)

        return _fit_depth_grouped(
            grid, weights, fit_group, N_BINS, leaf_axis=-2,
            fit_group_deep=fit_group, n_slots=n_slots)

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        """``configChunks``: how many chunks of configurations the fit's
        ``lax.map``s run over its depth groups, by the rules of
        ``fit_batch``, ``_fit_depth_grouped`` and ``_rf_config_chunk``
        (tests/test_multiclass_support.py holds them to the traced count);
        ``featSubset``: the per-tree column width the growers run compact
        at (`_rf_feature_table`), 0 where they run all ``features``."""
        attrs = super().fit_span_attrs(rows, features, grid, num_classes,
                                       sweep)
        if any("maxDepth" not in g for g in grid):
            return attrs
        n_trees = int(max(g.get("numTrees", 20.0) for g in grid))
        if sweep:
            n_trees = min(n_trees, _SWEEP_RF_TREES)
        feat_idx = _rf_feature_table(
            len(grid), n_trees, features,
            _rf_p_feat(features, self._task(num_classes)))[1]
        d_sub = 0 if feat_idx is None else feat_idx.shape[-1]
        S = min(rows, _SWEEP_HIST_SAMPLE if sweep else _HIST_SAMPLE)
        k = (max(num_classes, 2)
             if self._task(num_classes) == "classification" else 3)
        depths = [int(g["maxDepth"]) for g in grid]
        uniq = sorted(set(depths))
        slots = _SWEEP_SLOTS if sweep else _REFIT_SLOTS
        if len(uniq) > 1 and uniq[-1] > _MAX_HEAP_DEPTH:
            slots = max(slots, 2 ** max(
                [u for u in uniq if u <= _MAX_HEAP_DEPTH], default=0))
        chunks = 0
        for u in uniq:
            B = depths.count(u)
            cb = _rf_config_chunk(B, S, n_trees, u,
                                  slots if u > _MAX_HEAP_DEPTH else 0, k,
                                  features, N_BINS, d_sub)
            chunks += -(-B // cb)
        return dict(attrs, configChunks=chunks, featSubset=d_sub)

    def predict_batch(self, params, X, num_classes):
        edges = self._edges_of(params)
        task = self._task(num_classes)
        leaf = params["leaf"]
        if task == "classification" and num_classes <= 2:
            # binary: route only the class-1 probability column (see DT)
            leaf = leaf[..., 1:]
        if "base_lv" in params:
            out = _predict_rf_chain_batch(
                params["feat_lv"], params["bins_lv"], params["base_lv"],
                leaf, params["tree_mask"], edges, X,
                n_bins=edges.shape[-1] + 1)
        else:
            depth = _depth_of(params["leaf"].shape[-2])
            out = _predict_rf_batch(params["feat"], params["bins"],
                                    leaf, params["tree_mask"],
                                    edges, X, depth=depth,
                                    n_bins=edges.shape[-1] + 1)
        if task == "classification" and num_classes <= 2:
            return out[..., 0]
        return _shape_scores(out, num_classes, task)

    def predict_parts(self, fitted: FittedParams, X):
        params = {k: jnp.asarray(v)[None] for k, v in fitted.params.items()}
        out = self.predict_batch(params, X, fitted.num_classes)[0]
        return _parts_j(out, fitted.num_classes, self._task(fitted.num_classes))

    def predict_one(self, fitted: FittedParams, X):
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, jnp.asarray(X)).items()}


class GBTFamilyBase(_TreeFamilyBase):
    """reference OpGBTClassifier/Regressor (maxIter 20, stepSize 0.1 per
    DefaultSelectorParams). Spark's GBTClassifier is binary-only; so is this
    one — multiclass boosting lives in the XGBoost families."""

    lam_default = 0.0
    mcw_default = 0.0

    def default_grid(self, problem):
        return [{"maxDepth": d, "minInstancesPerNode": mi, "minInfoGain": mg,
                 "maxIter": 20, "stepSize": 0.1}
                for d in _DEPTHS for mi in (10, 100)
                for mg in (0.001, 0.01, 0.1)]

    def _gbt_task(self, num_classes):
        if "regression" in self.supports and len(self.supports) == 1:
            return "regression"
        return "multiclass" if num_classes > 2 else "binary"

    def fit_batch(self, X, y, weights, grid, num_classes, sweep=False):
        # GBT trains entirely on the split-search sample: sweep and refit
        # are the same program
        task = self._gbt_task(num_classes)
        iter_vals = np.asarray(_g(grid, "maxIter", 20.0))
        n_rounds = int(iter_vals.max())
        if sweep:
            # rank with truncated boosting; the winner refits at full
            # maxIter (boosting rounds are the sweep's serial-step floor;
            # proportional per-config scaling when the grid sweeps maxIter
            # itself — see _sweep_ensemble_cap)
            capped = _sweep_ensemble_cap(iter_vals, _SWEEP_GBT_ROUNDS,
                                         "maxIter")
            if capped is not None:
                n_rounds = int(capped.max())
                grid = dict(grid, maxIter=jnp.asarray(capped, jnp.float32))
        md = np.asarray(grid["maxDepth"], dtype=np.float64).reshape(-1)
        depth, slots = self._scan_shape(md, sweep)
        B = weights.shape[0]
        cb, C = self._round_lanes(B, depth, slots, int(X.shape[0]),
                                  int(X.shape[1]), num_classes, sweep)

        def one_raw(g, w):
            return _fit_gbt_batch(
                X, y, w, g["maxDepth"],
                _g(g, "minInstancesPerNode", 0.0), _g(g, "minInfoGain", 0.0),
                _g(g, "maxIter", 20.0), _g(g, "stepSize", 0.1),
                _g(g, "lambda", self.lam_default),
                _g(g, "minChildWeight", self.mcw_default),
                depth=depth, n_bins=N_BINS, num_classes=max(num_classes, 2),
                task=task, n_rounds=n_rounds, sweep=sweep, n_slots=slots)

        def one_call():
            if cb >= B:
                return one_raw(grid, weights)
            parts = []
            for c in range(-(-B // cb)):
                # wrap the tail chunk so every chunk shares one compile
                # plain-numpy index: grid values may be host constants
                # (the fused sweep program passes them that way), and
                # numpy cannot be indexed by a traced jnp constant
                idx = np.arange(c * cb, (c + 1) * cb) % B
                sub = {k2: v[idx] for k2, v in grid.items()}
                p = one_raw(sub, weights[idx])
                count = min((c + 1) * cb, B) - c * cb
                parts.append((idx[:count],
                              {k2: (v if k2 == "edges" else v[:count])
                               for k2, v in p.items()}))
            return _stitch_parts(B, parts)

        if sweep:
            return one_call()
        # a refit: the regrow on the split-search sample is all of it
        # (boosting has no exact leaf pass), one program under its span
        with _obs_span("refit.grow", family=self.name, trees=n_rounds,
                       depth=depth, slots=slots,
                       sampleRows=min(int(X.shape[0]), _HIST_SAMPLE),
                       **self._lane_attrs(cb * C)):
            return one_call()

    @staticmethod
    def _scan_shape(depths, sweep):
        """``(depth, slots)`` of the ONE boosting scan a grid's
        configurations share: the deepest ``maxDepth``, and 0 slots (complete
        heaps) up to `_MAX_HEAP_DEPTH`, else the slot-chain leaf budget."""
        d_max = int(depths.max())
        if d_max <= _MAX_HEAP_DEPTH:
            # no depth grouping: boosting rounds are a sequential scan, and
            # a second scan chain for shallow configs costs more than the
            # wasted deep levels (their active-mask already stops splitting)
            return d_max, 0
        # deep grid: ONE slot-chain scan for ALL configs at the deepest
        # depth. Boosting is step-count-bound (each of rounds x levels
        # sequential steps carries ~ms of small-op overhead at GBT's narrow
        # lane widths), so a merged 240-step scan beats a 120-step heap
        # scan PLUS a 240-step chain scan even though shallow configs ride
        # along through the deep levels (their max_depth mask stops
        # splitting; the budget keeps those levels narrow). Shallow
        # configs' trees still fit within the budget exactly when
        # 2^depth <= n_slots (chain == heap, test_capped_grower_matches_
        # heap_when_uncapped).
        n_slots = _SWEEP_SLOTS if sweep else _REFIT_SLOTS
        shallow = depths[depths <= _MAX_HEAP_DEPTH]
        if shallow.size:  # budget must hold a shallow config's full tree
            n_slots = max(n_slots, 2 ** int(shallow.max()))
        return d_max, n_slots

    def _round_lanes(self, B, depth, slots, rows, features, num_classes,
                     sweep):
        """``(cb, C)``: the configurations one boosting scan takes at once
        and the class planes of each; a round grows ``cb * C`` trees side by
        side. Config chunking under the SAME per-level histogram budget as
        RF (_LEVEL_HIST_ELEMS): the (Tb·nodes, d, n_bins, k) split pipeline
        scales with the feature count, and GBT's boosting scan otherwise
        runs every config at once — a 600-column text-hashed vector at depth
        12 would ask XLA for tens of GB."""
        C = (max(num_classes, 2)
             if self._gbt_task(num_classes) == "multiclass" else 1)
        nodes_w = (min(2 ** depth, slots) if slots
                   else 2 ** max(depth - 1, 0))
        per_cfg = C * nodes_w * features * N_BINS * 3
        cb = int(max(1, min(B, _LEVEL_HIST_ELEMS // max(per_cfg, 1))))
        # ...AND bound the (S, k·Wl·T_pad) masked-stat operand of the level
        # histogram itself: at the refit sample (65536 rows) a 200+-config
        # exact grid otherwise asks XLA for a >10 GB concatenate per level,
        # and the scheduler keeps ~3 pipeline stages of it alive (observed
        # 24.5 GB on the fidelity experiment's exact arm)
        S_est = min(rows, _SWEEP_HIST_SAMPLE if sweep else _HIST_SAMPLE)
        lanes_max = max((1 << 29) // max(S_est, 1), 192)
        return int(max(1, min(cb, lanes_max // (3 * nodes_w * C)))), C

    def fit_span_attrs(self, rows, features, grid, num_classes, sweep):
        """``treeLanes``: the trees a boosting round grows side by side
        (`_round_lanes`: configurations x folds x class planes, 1 for a
        binary or regression winner's refit), and ``treeLanesPadded``: the
        tree lanes the node histogram lays out for them
        (`histeng.kernels.tree_lane_shape`); their quotient is the share of
        the level contraction's lanes that carry a tree."""
        attrs = super().fit_span_attrs(rows, features, grid, num_classes,
                                       sweep)
        if any("maxDepth" not in g for g in grid):
            return attrs
        depth, slots = self._scan_shape(
            np.asarray([g["maxDepth"] for g in grid], np.float64), sweep)
        cb, C = self._round_lanes(len(grid), depth, slots, rows, features,
                                  num_classes, sweep)
        return dict(attrs, **self._lane_attrs(cb * C))

    @staticmethod
    def _lane_attrs(lanes):
        return {"treeLanes": lanes,
                "treeLanesPadded": tree_lane_shape(lanes)[0]}

    def predict_batch(self, params, X, num_classes):
        edges = self._edges_of(params)
        if "base_lv" in params:
            margins = _predict_gbt_chain_batch(
                params["feat_lv"], params["bins_lv"], params["base_lv"],
                params["leaf"], params["f0"], params["eta"],
                params["tree_mask"], edges, X,
                n_bins=edges.shape[-1] + 1)                      # (B, C, n)
        else:
            depth = _depth_of(params["leaf"].shape[-1])
            margins = _predict_gbt_batch(
                params["feat"], params["bins"], params["leaf"], params["f0"],
                params["eta"], params["tree_mask"], edges, X, depth=depth,
                n_bins=edges.shape[-1] + 1)                      # (B, C, n)
        task = self._gbt_task(num_classes)
        if task == "regression":
            return margins[:, 0, :]
        if task == "binary":
            return jax.nn.sigmoid(margins[:, 0, :])
        return jax.nn.softmax(jnp.swapaxes(margins, 1, 2), axis=-1)

    def predict_parts(self, fitted: FittedParams, X):
        params = {k: jnp.asarray(v)[None] for k, v in fitted.params.items()}
        task = self._gbt_task(fitted.num_classes)
        out = self.predict_batch(params, X, fitted.num_classes)[0]
        if task == "regression":
            return {"prediction": out}
        if task == "binary":
            prob = jnp.stack([1 - out, out], axis=1)
            pred = (out > 0.5).astype(jnp.float32)
            return {"prediction": pred, "probability": prob,
                    "rawPrediction": jnp.log(jnp.maximum(prob, 1e-12))}
        pred = out.argmax(axis=1).astype(jnp.float32)
        return {"prediction": pred, "probability": out,
                "rawPrediction": jnp.log(jnp.maximum(out, 1e-12))}

    def predict_one(self, fitted: FittedParams, X):
        return {k: np.asarray(v)
                for k, v in self.predict_parts(fitted, jnp.asarray(X)).items()}


# -- shared output shaping ---------------------------------------------------

def _depth_of(n_leaves: int) -> int:
    return int(np.log2(n_leaves))


def _shape_scores(out, num_classes, task):
    """(B, n, k) leaf outputs → family score convention: binary (B, n) p1;
    multiclass (B, n, C); regression (B, n)."""
    if task == "regression":
        return out[..., 0]
    if num_classes <= 2:
        return out[..., 1]
    return out[..., :num_classes]


def _parts_j(out, num_classes, task):
    """Prediction parts from family-convention scores, jit-traceable."""
    if task == "regression":
        return {"prediction": out}
    prob = jnp.stack([1 - out, out], axis=1) if out.ndim == 1 else out
    pred = prob.argmax(axis=1).astype(jnp.float32)
    return {"prediction": pred, "probability": prob,
            "rawPrediction": jnp.log(jnp.maximum(prob, 1e-12))}


# -- concrete registered families --------------------------------------------

class DecisionTreeClassifierFamily(DecisionTreeFamilyBase):
    name = "OpDecisionTreeClassifier"
    supports = frozenset({"binary", "multiclass"})


class DecisionTreeRegressorFamily(DecisionTreeFamilyBase):
    name = "OpDecisionTreeRegressor"
    supports = frozenset({"regression"})


class RandomForestClassifierFamily(RandomForestFamilyBase):
    name = "OpRandomForestClassifier"
    supports = frozenset({"binary", "multiclass"})


class RandomForestRegressorFamily(RandomForestFamilyBase):
    name = "OpRandomForestRegressor"
    supports = frozenset({"regression"})


class GBTClassifierFamily(GBTFamilyBase):
    name = "OpGBTClassifier"
    supports = frozenset({"binary"})


class GBTRegressorFamily(GBTFamilyBase):
    name = "OpGBTRegressor"
    supports = frozenset({"regression"})


class XGBoostClassifierFamily(GBTFamilyBase):
    """reference OpXGBoostClassifier (grid per DefaultSelectorParams:
    numRound {100} → maxIter, eta {0.1, 0.3} → stepSize, minChildWeight
    {1, 5, 10}); second-order splits with L2 ``lambda`` = 1 like XGBoost."""
    name = "OpXGBoostClassifier"
    supports = frozenset({"binary", "multiclass"})
    lam_default = 1.0
    mcw_default = 1.0

    def default_grid(self, problem):
        return [{"maxDepth": 6, "maxIter": 100, "stepSize": e,
                 "minChildWeight": m, "lambda": 1.0, "minInfoGain": 0.0,
                 "minInstancesPerNode": 0.0}
                for e in (0.1, 0.3) for m in (1.0, 5.0, 10.0)]


class XGBoostRegressorFamily(XGBoostClassifierFamily):
    name = "OpXGBoostRegressor"
    supports = frozenset({"regression"})


register_family(DecisionTreeClassifierFamily())
register_family(DecisionTreeRegressorFamily())
register_family(RandomForestClassifierFamily())
register_family(RandomForestRegressorFamily())
register_family(GBTClassifierFamily())
register_family(GBTRegressorFamily())
register_family(XGBoostClassifierFamily())
register_family(XGBoostRegressorFamily())
