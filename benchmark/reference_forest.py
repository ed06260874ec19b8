"""The plain reference of a tree-ensemble winner: numpy only, nothing of the
program imported, float64 accumulation.

From the winner's fitted tables, handed over as plain numpy arrays (a
random forest's ``feat_lv`` / ``bins_lv`` / ``base_lv`` slot chains or
``feat`` / ``bins`` complete heaps, its ``leaf`` values, ``tree_mask`` and
the bin ``edges``), and from raw float32 rows it recomputes

* the class-1 score: every tree descended row by row on raw float32 values
  against float32 thresholds (``edges[feature, bin]``), never on the
  program's bin codes; the mean of the trees' class-1 leaf values, summed in
  float64 (``forest_score``; a boosted ensemble's margin through a sigmoid:
  ``boosted_score``);
* the refit's exact part, retrained (``refit_leaves``): the rows the
  selector fitted on are routed down every tree and every leaf's class
  shares are recomputed from the rows' labels and weights in float64, to be
  compared with the program's leaf values over leaves that hold at least
  ``min_rows`` rows. A refit that skipped its leaf pass, ran it on the
  split-search sample, or summed in bfloat16 does not come out the same;
* the grower at refit size (``root_split_shortfall``): for the root split of
  every tree the 32-bin class histogram of the split's column is rebuilt
  from the rows the tree was grown on (``grown_rows``: the strided sample of
  the padded fit matrix), and the Gini gain the chosen bin leaves on the
  table in its own column is given in units of the best root split the rows
  offer. A forest's trees draw their columns and their bootstrap weights
  from the program's own random stream, which is not recoverable here: the
  histogram is the sample's unweighted one and a sound grower reads a small
  shortfall, not zero;
* a BOOSTED winner's refit, which trains on the split-search sample alone
  (``boosted_rounds``): round by round, the logistic loss's gradients at the
  reference's own running score, every leaf's Newton value from the rows
  that end in it, compared with the program's; and at EVERY node that
  splits, the root and all below it, the second-order gain of the stated
  split against the best any column and bin gives on the rows the reference
  finds in that node at that round's gradients (every tree sees every column
  and no row is resampled, so a sound grower's shortfall is its histograms'
  rounding);
* the bin-edge table (``sample_edges``): rebuilt from the grown rows by the
  stated rule, the inner quantiles of the split-search sample with its pad
  rows, and compared with the fit's (``edges_rel_diff``). The descents above
  use the fit's own table: an edge one float32 step off would send a row on
  the line the other way.

``precision="bf16"`` is the control: rows and thresholds rounded to
bfloat16 first, the step below the float32 the configuration states.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


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


def edges_of(params: Dict[str, Any]) -> np.ndarray:
    """(features, bins - 1) float32 bin edges, stored once a fit."""
    e = np.asarray(params["edges"], dtype=np.float32)
    return e[0] if e.ndim == 3 else e


def _thresholds(feat, bins, edges, precision):
    """float32 split thresholds ``edges[feature, bin]``; a bin at or past
    the edge table is a stopped node: +inf, every row goes left."""
    n_edges = edges.shape[-1]
    thr = _q(edges, precision)[feat, np.minimum(bins, n_edges - 1)]
    return np.where(bins >= n_edges, np.float32(np.inf), thr)


def n_trees(params: Dict[str, Any]) -> int:
    return int(np.asarray(params["leaf"]).shape[0])


def tree_depth(params: Dict[str, Any]) -> int:
    """Levels a row descends in every tree of the fit."""
    if "base_lv" in params:
        return int(np.asarray(params["feat_lv"]).shape[-2])
    return int(round(np.log2(np.asarray(params["feat"]).shape[-1] + 1)))


def leaf_slots(Xq: np.ndarray, params: Dict[str, Any], t: int,
               precision: str = "f32", lead: Tuple[int, ...] = ()
               ) -> np.ndarray:
    """(n,) leaf slot of every row of ``Xq`` in tree ``t`` (``lead`` indexes
    axes between the tree axis and the tables: a boosted fit's class axis)."""
    ix = (t,) + tuple(lead)
    edges = edges_of(params)
    rows = np.arange(Xq.shape[0])
    slot = np.zeros(Xq.shape[0], dtype=np.int64)
    if "base_lv" in params:              # slot chains: (depth, W) a tree
        feat = np.asarray(params["feat_lv"])[ix]
        base = np.asarray(params["base_lv"])[ix]
        thr = _thresholds(feat, np.asarray(params["bins_lv"])[ix], edges,
                          precision)
        for level in range(feat.shape[0]):
            go = Xq[rows, feat[level, slot]] > thr[level, slot]
            slot = base[level, slot] + go
        return slot
    feat = np.asarray(params["feat"])[ix]  # complete heap, level order
    thr = _thresholds(feat, np.asarray(params["bins"])[ix], edges, precision)
    for level in range(tree_depth(params)):
        idx = (2 ** level - 1) + slot
        slot = 2 * slot + (Xq[rows, feat[idx]] > thr[idx])
    return slot


def _blocks(n: int, block: int):
    return [(lo, min(lo + block, n)) for lo in range(0, n, block)]


def forest_score(params: Dict[str, Any], X: np.ndarray,
                 precision: str = "f32", block: int = 65536) -> np.ndarray:
    """A random forest's class-1 probability of every row of ``X``: the
    mean over its trees of the class-1 value of the leaf a row ends in."""
    leaf = np.asarray(params["leaf"], dtype=np.float32)      # (T, L, k)
    mask = np.asarray(params["tree_mask"], dtype=np.float64)
    out = np.zeros(X.shape[0], dtype=np.float64)
    for lo, hi in _blocks(X.shape[0], block):
        Xq = _q(X[lo:hi], precision)
        for t in np.nonzero(mask)[0]:
            slot = leaf_slots(Xq, params, int(t), precision)
            out[lo:hi] += mask[t] * leaf[t, slot, 1].astype(np.float64)
    return (out / max(float(mask.sum()), 1.0)).astype(np.float32)


def boosted_score(params: Dict[str, Any], X: np.ndarray,
                  precision: str = "f32", block: int = 65536) -> np.ndarray:
    """A boosted ensemble's class-1 probability: ``sigmoid(f0 + eta * sum
    of the rounds' leaf values)``."""
    leaf = np.asarray(params["leaf"], dtype=np.float32)      # (T, C, L)
    mask = np.asarray(params["tree_mask"], dtype=np.float64)
    f0 = float(np.asarray(params["f0"]).reshape(-1)[0])
    eta = float(np.asarray(params["eta"]).reshape(-1)[0])
    acc = np.zeros(X.shape[0], dtype=np.float64)
    for lo, hi in _blocks(X.shape[0], block):
        Xq = _q(X[lo:hi], precision)
        for t in np.nonzero(mask)[0]:
            slot = leaf_slots(Xq, params, int(t), precision, lead=(0,))
            acc[lo:hi] += mask[t] * leaf[t, 0, slot].astype(np.float64)
    z = (f0 + eta * acc).astype(np.float32)
    return (1.0 / (1.0 + np.exp(-z, dtype=np.float32))).astype(np.float32)


SCORES = {"OpRandomForestClassifier": forest_score,
          "OpGBTClassifier": boosted_score}


def class1_score(family: str, params: Dict[str, Any], X: np.ndarray,
                 precision: str = "f32") -> np.ndarray:
    """KeyError for a family that is no tree ensemble."""
    return SCORES[family](params, np.asarray(X, dtype=np.float32), precision)


# ---------------------------------------------------------------------------
# The refit's exact part, retrained
# ---------------------------------------------------------------------------

def leaf_class_sums(params: Dict[str, Any], X: np.ndarray, y: np.ndarray,
                    w: np.ndarray, block: int = 65536) -> np.ndarray:
    """(T, L, 2) float64: every leaf's summed weight of the rows of each
    class that end in it, over all rows of ``X``."""
    L = int(np.asarray(params["leaf"]).shape[1])
    T = n_trees(params)
    pos = (np.asarray(y) > 0.5)
    w = np.asarray(w, dtype=np.float64)
    sums = np.zeros((T, L, 2), dtype=np.float64)
    for lo, hi in _blocks(X.shape[0], block):
        Xb = np.asarray(X[lo:hi], dtype=np.float32)
        w1 = w[lo:hi] * pos[lo:hi]
        w0 = w[lo:hi] - w1
        for t in range(T):
            slot = leaf_slots(Xb, params, t)
            sums[t, :, 0] += np.bincount(slot, weights=w0, minlength=L)[:L]
            sums[t, :, 1] += np.bincount(slot, weights=w1, minlength=L)[:L]
    return sums


def refit_leaves(params: Dict[str, Any], sums: np.ndarray, min_rows: float
                 ) -> Tuple[float, int]:
    """(largest distance between the program's leaf values and the class
    shares recomputed from ``sums``, leaves compared): over the leaves of
    the trees in use that hold at least ``min_rows`` rows' weight."""
    leaf = np.asarray(params["leaf"], dtype=np.float64)       # (T, L, k)
    mask = np.asarray(params["tree_mask"]) > 0
    total = sums.sum(-1)
    held = (total >= float(min_rows)) & mask[:, None]
    if not held.any():
        return float("nan"), 0
    share = sums / np.maximum(total, 1e-300)[..., None]
    diff = np.abs(leaf[..., :2] - share).max(-1)
    return float(diff[held].max()), int(held.sum())


def with_leaves_from(params: Dict[str, Any], sums: np.ndarray
                     ) -> Dict[str, Any]:
    """``params`` with every leaf's values replaced by the class shares of
    ``sums`` (an empty leaf keeps the program's): the control's forest."""
    total = sums.sum(-1)
    share = sums / np.maximum(total, 1e-300)[..., None]
    leaf = np.array(params["leaf"], dtype=np.float32)
    leaf[..., :2] = np.where((total > 0)[..., None], share, leaf[..., :2])
    return dict(params, leaf=leaf)


def with_splits_moved(params: Dict[str, Any], by: int, level: int = 0
                      ) -> Dict[str, Any]:
    """``params`` with every split at ``level`` of every tree moved ``by``
    bins along its column (kept inside the edge table): the control's
    grower, which did not take the best split there."""
    chain = "base_lv" in params
    key = "bins_lv" if chain else "bins"
    bins = np.array(params[key])
    n_edges = edges_of(params).shape[-1]
    W = bins.shape[-1]
    at = (np.arange(level * W, (level + 1) * W) if chain
          else np.arange(2 ** level - 1, 2 ** (level + 1) - 1))
    flat = bins.reshape(bins.shape[0], -1)      # a view: one class plane
    part = flat[:, at]
    flat[:, at] = np.where(part < n_edges, (part + by) % n_edges, part)
    return dict(params, **{key: bins})


def with_roots_moved(params: Dict[str, Any], by: int) -> Dict[str, Any]:
    """``with_splits_moved`` at the root."""
    return with_splits_moved(params, by, 0)


# ---------------------------------------------------------------------------
# The grower at refit size
# ---------------------------------------------------------------------------

def grown_rows(fitted: int, padded: int, sample: int) -> np.ndarray:
    """Which of the ``fitted`` rows a refit's trees are grown on, by the
    program's stated rule: the fit matrix is padded to ``padded`` rows of
    weight zero and ``sample`` evenly spaced rows of it are the split-search
    sample (``linspace(0, padded - 1, sample)`` floored); those past the
    fitted rows carry no weight."""
    if padded <= sample:
        return np.arange(min(fitted, padded))
    at = np.linspace(0, padded - 1, sample).astype(np.int64)
    return at[at < fitted]


def sample_edges(X_grown: np.ndarray, fitted: int, padded: int, sample: int,
                 n_bins: int = 32) -> np.ndarray:
    """(features, n_bins - 1) float32 bin edges by the program's stated
    rule, rebuilt from the rows: the ``n_bins - 1`` inner quantiles (linear
    interpolation between order statistics) of every column over the WHOLE
    split-search sample, which beside the grown rows ``X_grown`` holds the
    padded matrix's rows of zeros that the sample's positions fall on."""
    pads = (sample - len(X_grown)) if padded > sample \
        else max(padded - fitted, 0)
    Xs = np.asarray(X_grown, dtype=np.float64)
    if pads:
        Xs = np.concatenate([Xs, np.zeros((pads, Xs.shape[1]))])
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(Xs, qs, axis=0).T.astype(np.float32)


def edges_rel_diff(params: Dict[str, Any], rebuilt: np.ndarray) -> float:
    """Largest distance between the fit's edge table and ``rebuilt``, in
    units of each column's spread of edges."""
    own = edges_of(params).astype(np.float64)
    spread = np.maximum(rebuilt.max(-1) - rebuilt.min(-1), 1e-30)
    return float((np.abs(own - rebuilt) / spread[:, None]).max())


def _gini_gain(left: np.ndarray, total: np.ndarray) -> np.ndarray:
    """Gini gain of every candidate split: ``left`` (bins - 1, 2) class
    sums of the rows at or under each edge, ``total`` (2,) of all rows."""
    right = total[None, :] - left
    nl, nr, n = left.sum(-1), right.sum(-1), float(total.sum())

    def impurity(s, m):
        return 1.0 - ((s / np.maximum(m, 1e-300)[..., None]) ** 2).sum(-1)

    gain = (impurity(total[None, :], np.array([n]))
            - nl / n * impurity(left, nl) - nr / n * impurity(right, nr))
    return np.where((nl > 0) & (nr > 0), gain, 0.0)


def _column_gains(x: np.ndarray, pos: np.ndarray, edges: np.ndarray,
                  total: np.ndarray) -> np.ndarray:
    """Gini gain of every edge of one column as a root split."""
    code = (np.asarray(x, dtype=np.float32)[:, None] > edges[None, :]).sum(-1)
    bins = edges.shape[-1] + 1
    hist = np.stack([np.bincount(code[~pos], minlength=bins),
                     np.bincount(code[pos], minlength=bins)],
                    axis=-1).astype(np.float64)
    return _gini_gain(np.cumsum(hist, axis=0)[:-1], total)


def root_split_shortfall(params: Dict[str, Any], Xs: np.ndarray,
                         ys: np.ndarray) -> Tuple[float, float, int]:
    """(largest, mean, roots compared) over the trees in use of ``(best of
    the root's column - chosen) / best of any column``: the Gini gain, on
    the rows ``Xs`` / ``ys`` the trees were grown on, that the root's chosen
    bin leaves on the table in its own column, in units of the best root
    split the rows offer (a tree draws its columns, so its root need not be
    on the best one; on a column that carries nothing both gains are noise
    and the shortfall reads 0). A root that never split is not compared."""
    edges = edges_of(params)
    chain = "base_lv" in params
    feat = np.asarray(params["feat_lv" if chain else "feat"])
    bins = np.asarray(params["bins_lv" if chain else "bins"])
    mask = np.asarray(params["tree_mask"]) > 0
    pos = np.asarray(ys) > 0.5
    total = np.array([float((~pos).sum()), float(pos.sum())])
    gains = {}

    def column(f):
        if f not in gains:
            gains[f] = _column_gains(Xs[:, f], pos, edges[f], total)
        return gains[f]

    best_any = max(float(column(f).max()) for f in range(edges.shape[0]))
    out = []
    for t in np.nonzero(mask)[0]:
        f = int(feat[t, 0, 0] if chain else feat[t, 0])
        b = int(bins[t, 0, 0] if chain else bins[t, 0])
        if b >= edges.shape[-1] or best_any <= 0:
            continue
        out.append((float(column(f).max()) - float(column(f)[b])) / best_any)
    if not out:
        return float("nan"), float("nan"), 0
    return float(max(out)), float(np.mean(out)), len(out)


# ---------------------------------------------------------------------------
# A boosted winner: its rounds retrained on the rows they were grown on
# ---------------------------------------------------------------------------

def _gain_gh(GL, HL, G, H, lam):
    """Second-order gain of every candidate split: left sums ``GL`` / ``HL``
    (..., bins - 1) of nodes with sums ``G`` / ``H`` (..., 1)."""
    GR, HR = G - GL, H - HL
    return (GL ** 2 / (HL + lam + 1e-12) + GR ** 2 / (HR + lam + 1e-12)
            - G ** 2 / (H + lam + 1e-12))


def node_split_shortfalls(codes: np.ndarray, slot: np.ndarray, g: np.ndarray,
                          h: np.ndarray, feat: np.ndarray, bins: np.ndarray,
                          n_bins: int, lam: float, child_rows: float
                          ) -> np.ndarray:
    """One level of one tree: for every slot that splits there (``bins``
    under ``n_bins - 1``), ``(best - chosen) / best`` of the second-order
    gain, on the rows ``slot`` says the node holds and their ``g`` / ``h``:
    ``best`` over every column and bin that leaves ``child_rows`` rows and
    some curvature on both sides, ``chosen`` the split the tables state (1.0
    where that split is no such candidate). ``codes`` (n, d) are the rows'
    bin codes; ``feat`` / ``bins`` (W,) the level's tables."""
    n, d = codes.shape
    W = len(bins)
    split = np.nonzero(bins < n_bins - 1)[0]
    if not len(split):
        return np.zeros(0)
    at = np.full(W, -1, dtype=np.int64)
    at[split] = np.arange(len(split))
    rows = np.nonzero(at[slot] >= 0)[0]
    cell = ((at[slot[rows]] * d)[:, None] + np.arange(d)[None, :]) * n_bins \
        + codes[rows]
    size = len(split) * d * n_bins

    def sums(w):
        flat = np.bincount(cell.reshape(-1), minlength=size, weights=(
            None if w is None else np.repeat(w[rows], d)))
        return np.cumsum(flat.reshape(len(split), d, n_bins), axis=-1)

    G, H, C = sums(g), sums(h), sums(None)
    GL, HL, CL = G[..., :-1], H[..., :-1], C[..., :-1]
    Gt, Ht, Ct = G[..., -1:], H[..., -1:], C[..., -1:]
    gain = _gain_gh(GL, HL, Gt, Ht, lam)
    valid = ((CL >= child_rows) & (Ct - CL >= child_rows)
             & (HL > 0) & (Ht - HL > 0))
    gain = np.where(valid, gain, -np.inf)
    best = gain.reshape(len(split), -1).max(-1)
    chosen = gain[np.arange(len(split)), feat[split], bins[split]]
    with np.errstate(invalid="ignore", divide="ignore"):
        short = np.where(np.isfinite(chosen) & (best > 0),
                         (best - chosen) / best, 1.0)
    return short


def boosted_rounds(params: Dict[str, Any], Xs: np.ndarray, ys: np.ndarray,
                   min_rows: float, lam: float = 0.0, frozen: bool = False,
                   row_weight: float = 1.0) -> Dict[str, float]:
    """A boosted binary refit retrained round by round on the rows ``Xs`` /
    ``ys`` it was grown on. Returns ``leaf_max_abs_diff`` (largest distance
    between the program's leaf values and the reference's),
    ``leaves_compared``, ``split_shortfall_max`` / ``_mean`` over EVERY
    node that splits (``splits_compared`` of them, ``splits_short`` with a
    shortfall over 1e-9), ``root_shortfall_max`` and ``roots_compared``.

    Round ``t``: the gradients ``p - y`` and curvatures ``max(p (1 - p),
    1e-6)`` of the logistic loss at the REFERENCE's own running score, in
    float64; the rows routed down the program's tree ``t`` level by level;
    at every node that splits, the second-order gain of the split the tables
    state against the best gain any column and bin gives on the rows the
    reference finds in that node, at this round's gradients (every tree sees
    every column and no row is resampled, so a sound grower's shortfall is
    its histograms' rounding; a candidate has to leave ``min_rows /
    row_weight`` rows on either side: a grown row stands for ``row_weight``
    fitted ones); a leaf's value ``-G / (H + lam)`` from the rows that end
    in it, compared with the program's over leaves of at least ``min_rows``
    rows; the running score then moves by ``eta`` times the reference's own
    leaf values. ``frozen`` is the control: the running score never
    moves."""
    edges = edges_of(params)
    leaf = np.asarray(params["leaf"], dtype=np.float64)       # (T, C, L)
    mask = np.asarray(params["tree_mask"]) > 0
    eta = float(np.asarray(params["eta"]).reshape(-1)[0])
    chain = "base_lv" in params
    feat = np.asarray(params["feat_lv" if chain else "feat"])
    bins = np.asarray(params["bins_lv" if chain else "bins"])
    base = np.asarray(params["base_lv"]) if chain else None
    depth = tree_depth(params)
    L = leaf.shape[-1]
    Xs = np.asarray(Xs, dtype=np.float32)
    y = (np.asarray(ys) > 0.5).astype(np.float64)
    codes = np.stack([(Xs[:, f, None] > edges[f][None, :]).sum(-1)
                      for f in range(edges.shape[0])], axis=1)
    n_bins = edges.shape[-1] + 1
    child_rows = max(float(min_rows) / float(row_weight), 1e-6)
    F = np.full(len(y), float(np.asarray(params["f0"]).reshape(-1)[0]))
    worst, compared, short, roots = 0.0, 0, [], []
    for t in np.nonzero(mask)[0]:
        p = 1.0 / (1.0 + np.exp(-F))
        g, h = p - y, np.maximum(p * (1.0 - p), 1e-6)
        gw, hw = g * row_weight, h * row_weight
        slot = np.zeros(len(y), dtype=np.int64)
        for level in range(depth):
            if chain:
                f_l, b_l = feat[t, 0, level], bins[t, 0, level]
            else:       # complete heap, level order: 2^level slots
                lo = 2 ** level - 1
                f_l = feat[t, 0, lo:lo + 2 ** level]
                b_l = bins[t, 0, lo:lo + 2 ** level]
            got = node_split_shortfalls(codes, slot, gw, hw, f_l, b_l,
                                        n_bins, lam, child_rows)
            short.extend(got.tolist())
            if level == 0:
                roots.extend(got.tolist())
            go = codes[np.arange(len(y)), f_l[slot]] > b_l[slot]
            slot = (base[t, 0, level][slot] + go) if chain \
                else 2 * slot + go
        G = np.bincount(slot, weights=g, minlength=L)[:L]
        H = np.bincount(slot, weights=h, minlength=L)[:L]
        rows = np.bincount(slot, minlength=L)[:L]
        own = -G / (H + lam / row_weight + 1e-12 / row_weight)
        held = rows >= float(min_rows)
        if held.any():
            worst = max(worst, float(np.abs(own - leaf[t, 0])[held].max()))
            compared += int(held.sum())
        if not frozen:
            F = F + eta * np.where(rows[slot] > 0, own[slot], 0.0)
    short = np.asarray(short)
    return {
        "leaf_max_abs_diff": worst, "leaves_compared": compared,
        "split_shortfall_max": float(short.max()) if len(short)
        else float("nan"),
        "split_shortfall_mean": float(short.mean()) if len(short)
        else float("nan"),
        "splits_compared": int(len(short)),
        "splits_short": int((short > 1e-9).sum()),
        "root_shortfall_max": float(max(roots)) if roots else float("nan"),
        "roots_compared": int(len(roots))}
