"""Pallas TPU kernel: fused multi-level forest descent.

The full-data passes of tree fitting and scoring (models/trees.py) both do

    node[s, t]  =  leaf reached by row s in tree t          (descent)
    then either Σ_s aug[s, k]·1[node==l]                    (exact leaf stats)
    or          Σ_t leaf[t, node[s,t], k]                   (prediction)

Done per level in XLA this materializes (n, T·m) decision matrices and
(n, T·L) leaf one-hots in HBM — at 1M rows × 50 trees that is gigabytes per
config and was ~97% of the RandomForest sweep's wall clock (356 ms per
config; the whole default RF grid 12.8 s). This kernel performs the whole
descent for a row block in VMEM:

- per level, the split feature's bin code is *gathered by matmul*: a (d, T·m)
  one-hot of the level's split features against the row block's codes —
  gathers are scatters' evil twin on TPU, but a gather whose index set is
  shared by every row IS a matmul, and matmuls are what the MXU is for;
- the go-right bit is one f32 compare against the level's bin thresholds
  (sentinel bin = n_bins ⇒ always left, which also makes padded trees and
  stopped nodes route to leaf 0 with zero extra logic);
- the per-row node is selected from the (T·m) candidate bits by an equality
  mask against a lane iota and a tiny (T·m, T) group-sum matmul;
- the leaf one-hot for the final reduction never leaves VMEM: leaf sums are
  accumulated into a (k, T·L) f32 block across the row grid; predictions are
  a (R, T·L)×(T·L, k) matmul against the leaf-value table.

HBM traffic per config drops to: read codes once (n·d int32), write either
(T, L, k) sums or (n, k) predictions. No (n, T·m) intermediate exists.

Replaces the reference's per-executor SparkML `Node.predictImpl` recursion
and the XGBoost JNI predictor (reference: SURVEY §2.9) with a TPU-native
kernel. Layout notes: lanes are j-major — lane = j·T_pad + t — because
`_tile_lanes` (Mosaic RepeatOp on TPU) tiles whole vectors along lanes, so
repeating the (R, T_pad)
node vector m times lines tree t up with every candidate j at lane j·T_pad+t.

Fallback: non-TPU backends (CPU test mesh, dry runs) and shapes outside the
VMEM envelope (depth > 7 or > 128 trees) run the same math as XLA einsums.
Dispatch reads the backend at trace time (see the histeng/kernels.py
note).
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from ..histeng.kernels import _interpret, _pad_to, _tile_lanes, _use_pallas

_BLK_R = 128  # rows per VMEM block
_MAX_DEPTH_PALLAS = 7  # beyond this the (R, T·m) block outgrows VMEM
_MAX_TREES_PALLAS = 128


def _t_pad(T: int, depth: int) -> int:
    """Tree-axis padding: a multiple of 64 keeps every RAGGED level's lane
    width (T_pad × even node count) a 128-multiple AND an exact multiple of
    T_pad, so `_tile_lanes(node, m_eff)` lands each tree at lane
    j·T_pad + t without any in-kernel pad."""
    return max(64, _pad_to(T, 64))


def _m_eff(level: int) -> int:
    """Per-level node-lane count: the natural 2^level, floored at 2 so the
    lane width stays a 128-multiple (T_pad is a multiple of 64)."""
    return max(2, 2 ** level)


def _level_tables(feat_heap: jnp.ndarray, bin_heap: jnp.ndarray, depth: int,
                  n_bins: int, T_pad: int):
    """j-major RAGGED per-level split tables, concatenated flat.

    Level ``l`` occupies ``T_pad·_m_eff(l)`` lanes (lane = j·T_pad + t) —
    ~3x fewer total lanes than padding every level to the deepest width.
    Sentinel bins fill every padded slot (tree, level-width, stopped node).
    Returns ((1, Σw) f_flat, (1, Σw) b_flat)."""
    T = feat_heap.shape[0]
    f_rows, b_rows = [], []
    for level in range(depth):
        base, m = 2 ** level - 1, 2 ** level
        m_eff = _m_eff(level)
        f = jnp.pad(feat_heap[:, base:base + m],
                    ((0, T_pad - T), (0, m_eff - m)))
        b = jnp.pad(bin_heap[:, base:base + m],
                    ((0, T_pad - T), (0, m_eff - m)),
                    constant_values=n_bins)
        # (T_pad, m_eff) -> j-major flat: lane j*T_pad + t
        f_rows.append(f.T.reshape(-1))
        b_rows.append(b.T.reshape(-1))
    return jnp.concatenate(f_rows)[None, :].astype(jnp.int32), \
        jnp.concatenate(b_rows)[None, :].astype(jnp.int32)


def _descend(codes_f, f_flat_ref, b_flat_ref, *, depth, T_pad, d_pad):
    """In-kernel: (R, d_pad) f32 codes → (R, T_pad) int32 leaf ids.

    Ragged levels: level l reads its own T_pad·_m_eff(l)-lane slice of the
    flat split tables, so early levels do 1/m_max-th the deepest level's
    VPU/MXU work instead of padding up to it."""
    R = codes_f.shape[0]
    codes_bf = codes_f.astype(jnp.bfloat16)
    node = jnp.zeros((R, T_pad), jnp.int32)
    off = 0
    for level in range(depth):
        m_eff = _m_eff(level)
        w = T_pad * m_eff
        f_row = f_flat_ref[0:1, off:off + w]                  # (1, w)
        b_row = b_flat_ref[0:1, off:off + w]
        off += w
        d_iota = jax.lax.broadcasted_iota(jnp.int32, (d_pad, w), 0)
        sel = (d_iota == f_row).astype(jnp.bfloat16)          # (d_pad, w)
        code_sel = jnp.dot(codes_bf, sel,
                           preferred_element_type=jnp.float32)  # (R, w)
        go_lane = (code_sel > b_row.astype(jnp.float32)
                   ).astype(jnp.bfloat16)
        node_rep = _tile_lanes(node, m_eff)                   # (R, w)
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, w), 1)
        oh = (node_rep == lane // T_pad).astype(jnp.bfloat16)
        gl = jax.lax.broadcasted_iota(jnp.int32, (w, T_pad), 0) % T_pad
        gt = jax.lax.broadcasted_iota(jnp.int32, (w, T_pad), 1)
        G = (gl == gt).astype(jnp.bfloat16)                   # (w, T_pad)
        go = jnp.dot(go_lane * oh, G,
                     preferred_element_type=jnp.float32)      # (R, T_pad)
        node = 2 * node + (go > 0.5).astype(jnp.int32)
    return node


def _leaf_onehot(node, *, depth, T_pad):
    """(R, T_pad) leaf ids → (R, T_pad·L) bf16 one-hot, lane = leaf·T_pad+t."""
    R = node.shape[0]
    L = 2 ** depth
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, T_pad * L), 1)
    node_rep = _tile_lanes(node, L)
    return (node_rep == lane // T_pad).astype(jnp.bfloat16)


def _leaf_sums_pallas(codes, f_lvls, b_lvls, aug, *, depth, n_bins, T_pad):
    from jax.experimental import pallas as pl

    n, d = codes.shape
    k = aug.shape[1]
    d_pad = _pad_to(d, 128)
    k_pad = _pad_to(k, 8)
    L = 2 ** depth
    blk_r = _BLK_R
    n_pad = _pad_to(n, blk_r)
    codes_p = jnp.pad(codes.astype(jnp.int32),
                      ((0, n_pad - n), (0, d_pad - d)))
    aug_p = jnp.pad(aug.astype(jnp.float32),
                    ((0, n_pad - n), (0, k_pad - k)))  # zero rows: no-op

    def kernel(codes_ref, f_ref, b_ref, aug_ref, out_ref):
        r = pl.program_id(0)
        node = _descend(codes_ref[:].astype(jnp.float32), f_ref, b_ref,
                        depth=depth, T_pad=T_pad, d_pad=d_pad)
        l_oh = _leaf_onehot(node, depth=depth, T_pad=T_pad)
        # (k, T_pad·L): lanes wide, accumulator small. precision=HIGHEST:
        # default matmul precision truncates f32 operands to bf16 — exact for
        # the 0/1 one-hot, NOT for the stat values (leaf stats serve
        # predictions and must not round)
        part = jax.lax.dot_general(
            aug_ref[:], l_oh.astype(jnp.float32),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

        @pl.when(r == 0)
        def _():
            out_ref[:] = part

        @pl.when(r > 0)
        def _():
            out_ref[:] += part

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((k_pad, T_pad * L), jnp.float32),
        grid=(n_pad // blk_r,),
        in_specs=[
            pl.BlockSpec((blk_r, d_pad), lambda r: (r, 0)),
            pl.BlockSpec(f_lvls.shape, lambda r: (0, 0)),
            pl.BlockSpec(b_lvls.shape, lambda r: (0, 0)),
            pl.BlockSpec((blk_r, k_pad), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((k_pad, T_pad * L), lambda r: (0, 0)),
        interpret=_interpret(),
    )(codes_p, f_lvls, b_lvls, aug_p)
    # (k, leaf·T_pad+t) -> (T_pad, L, k)
    return out.reshape(k_pad, L, T_pad).transpose(2, 1, 0)[:, :, :k]


def _predict_pallas(codes, f_lvls, b_lvls, leaf_flat, *, depth, n_bins,
                    T_pad):
    from jax.experimental import pallas as pl

    n, d = codes.shape
    k = leaf_flat.shape[1]
    d_pad = _pad_to(d, 128)
    k_pad = _pad_to(k, 128)
    L = 2 ** depth
    blk_r = _BLK_R
    n_pad = _pad_to(n, blk_r)
    codes_p = jnp.pad(codes.astype(jnp.int32),
                      ((0, n_pad - n), (0, d_pad - d)))
    leaf_p = jnp.pad(leaf_flat.astype(jnp.float32),
                     ((0, 0), (0, k_pad - k)))

    def kernel(codes_ref, f_ref, b_ref, leaf_ref, out_ref):
        node = _descend(codes_ref[:].astype(jnp.float32), f_ref, b_ref,
                        depth=depth, T_pad=T_pad, d_pad=d_pad)
        l_oh = _leaf_onehot(node, depth=depth, T_pad=T_pad)
        out_ref[:] = jnp.dot(l_oh.astype(jnp.float32), leaf_ref[:],
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        grid=(n_pad // blk_r,),
        in_specs=[
            pl.BlockSpec((blk_r, d_pad), lambda r: (r, 0)),
            pl.BlockSpec(f_lvls.shape, lambda r: (0, 0)),
            pl.BlockSpec(b_lvls.shape, lambda r: (0, 0)),
            pl.BlockSpec(leaf_flat.shape[:1] + (k_pad,), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_r, k_pad), lambda r: (r, 0)),
        interpret=_interpret(),
    )(codes_p, f_lvls, b_lvls, leaf_p)
    return out[:n, :k]


# ---------------------------------------------------------------------------
# XLA fallback: identical math, per-level feature-select matmuls
# ---------------------------------------------------------------------------

def route_codes_xla(codes: jnp.ndarray, feat_heap: jnp.ndarray,
                    bin_heap: jnp.ndarray, depth: int,
                    n_bins: int) -> jnp.ndarray:
    """(n, T) leaf assignments via per-level feature-select matmuls.

    The gather codes[s, feat] is a matmul against the (d, T·m) split-feature
    one-hot — even in XLA this replaces the old (d·n_bins)-wide comparison
    contraction (route_matmul) at 1/n_bins-th the FLOPs."""
    n, d = codes.shape
    T = feat_heap.shape[0]
    codes_f = codes.astype(jnp.bfloat16)
    node = jnp.zeros((n, T), jnp.int32)
    for level in range(depth):
        base, m = 2 ** level - 1, 2 ** level
        f_lvl = feat_heap[:, base:base + m]                  # (T, m)
        b_lvl = bin_heap[:, base:base + m]
        sel = (f_lvl.reshape(-1)[None, :]
               == jnp.arange(d, dtype=jnp.int32)[:, None]
               ).astype(jnp.bfloat16)                        # (d, T·m)
        code_sel = (codes_f @ sel).reshape(n, T, m)
        go_all = code_sel > b_lvl[None].astype(jnp.bfloat16)
        n_oh = node[:, :, None] == jnp.arange(m, dtype=jnp.int32)
        go = jnp.any(go_all & n_oh, axis=2)
        node = 2 * node + go.astype(jnp.int32)
    return node


def _leaf_sums_xla(codes, feat_heap, bin_heap, aug, *, depth, n_bins):
    n = codes.shape[0]
    T = feat_heap.shape[0]
    L = 2 ** depth
    node = route_codes_xla(codes, feat_heap, bin_heap, depth, n_bins)
    comb = node + (jnp.arange(T, dtype=jnp.int32) * L)[None, :]
    l_oh = (comb[:, :, None]
            == jnp.arange(T * L, dtype=jnp.int32).reshape(1, T, L)
            ).astype(jnp.float32).reshape(n, T * L)
    out = jnp.einsum("na,nk->ak", l_oh, aug.astype(jnp.float32),
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(T, L, -1)


def _predict_xla(codes, feat_heap, bin_heap, leaf, *, depth, n_bins):
    n = codes.shape[0]
    T, L, k = leaf.shape
    node = route_codes_xla(codes, feat_heap, bin_heap, depth, n_bins)
    comb = node + (jnp.arange(T, dtype=jnp.int32) * L)[None, :]
    l_oh = (comb[:, :, None]
            == jnp.arange(T * L, dtype=jnp.int32).reshape(1, T, L)
            ).astype(jnp.float32).reshape(n, T * L)
    return jnp.einsum("na,ak->nk", l_oh, leaf.reshape(T * L, k),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def _pallas_ok(depth: int, T: int) -> bool:
    return (_use_pallas() and depth <= _MAX_DEPTH_PALLAS
            and T <= _MAX_TREES_PALLAS)


def _check_bins(n_bins: int) -> None:
    """Descent casts int32 bin codes to bf16, which represents integers
    exactly only up to 256 — larger bin codes would silently misroute."""
    if n_bins > 256:
        raise ValueError(
            f"n_bins={n_bins} > 256: bin codes are routed in bfloat16, "
            f"which is exact only for codes <= 256")


def forest_leaf_sums(codes: jnp.ndarray, feat_heap: jnp.ndarray,
                     bin_heap: jnp.ndarray, aug: jnp.ndarray, *,
                     depth: int, n_bins: int) -> jnp.ndarray:
    """Exact leaf statistics for a forest in one fused pass.

    codes: (n, d) int32 bin codes; feat_heap/bin_heap: (T, 2^depth−1)
    complete-heap splits (sentinel bin ≥ n_bins ⇒ route left);
    aug: (n, k) f32 per-row stats (pad rows with zeros — they add nothing).
    Returns (T, L, k) f32 with L = 2^depth: sums of aug over rows landing in
    each (tree, leaf).
    """
    _check_bins(n_bins)
    T = feat_heap.shape[0]
    if not _pallas_ok(depth, T):
        return _leaf_sums_xla(codes, feat_heap, bin_heap, aug,
                              depth=depth, n_bins=n_bins)
    T_pad = _t_pad(T, depth)
    fh = jnp.pad(feat_heap, ((0, T_pad - T), (0, 0)))
    bh = jnp.pad(bin_heap, ((0, T_pad - T), (0, 0)),
                 constant_values=n_bins)
    f_lvls, b_lvls = _level_tables(fh, bh, depth, n_bins, T_pad)
    out = _leaf_sums_pallas(codes, f_lvls, b_lvls, aug,
                            depth=depth, n_bins=n_bins, T_pad=T_pad)
    return out[:T]


# ---------------------------------------------------------------------------
# Slot-chain ("leaf budget") trees: arbitrary depth at bounded width
#
# A complete heap doubles its level width every level (2^l nodes), which caps
# the practical depth at ~7: the descent's per-level lane width T_pad·2^l and
# the final (R, T_pad·2^depth) leaf one-hot outgrow VMEM, and the grower's
# histograms outgrow HBM. The reference's default grids include maxDepth 12
# (DefaultSelectorParams.scala:37), so deep trees get a second representation:
# per-level SLOT tables of static width W (the leaf budget — every split adds
# exactly one net slot, so a W-slot chain holds any tree with ≤ W leaves,
# grown level-wise with the best-gain splits kept, the XGBoost 'lossguide' /
# LightGBM num_leaves design point). Routing is
#
#     slot' = base[slot] + go,   go = codes[:, feat[slot]] > bin[slot]
#
# where a split slot's base points at its child pair, a finished leaf's base
# carries it forward unchanged (sentinel bin ⇒ go 0), and the slot after the
# last level IS the leaf id in [0, W). Every per-level operand is ≤ T_pad·W
# lanes regardless of depth, so depth 12 runs in the same VMEM envelope as a
# depth-5 heap. Shallow complete heaps embed exactly (base = 2·slot), letting
# mixed-depth grids share one predict program.
# ---------------------------------------------------------------------------

# The chain kernels' block body keeps only the work that selects a row's
# slot (PR 42). Per level there is ONE matmul a chunk of its lanes and, for
# the product, a compare and a select:
# - a lane's column of the select table holds a one at its split feature and,
#   at the two rows that meet the code block's two columns of ones, the
#   lane's threshold and base (`_chain_tables`), so the product reads
#   ``code + 256 - bin + 512·base``: the go bit and the next slot's base in
#   one exact integer. A block builds each chunk's columns from the lanes'
#   three numbers (`_select_columns`: only the rows up to the row's last
#   code can hold anything), whatever the table's width;
# - a lane keeps its number where the row's slot is the lane's. One lane a
#   tree matches, so the selects and the sum of the 128-lane slabs after them
#   are the sum over a tree's slots: no group-sum matmul; two lane rolls add
#   a slab's four tree groups and leave the slot tiled four times along the
#   lanes, as the next level's compare wants it;
# - the lanes of a level are walked in chunks of `_LANE_CHUNK`, so the
#   (rows, chunk) product and its select are the only wide temporaries, and a
#   grid step takes `_chain_block_rows(n)` rows: 128 (on the chip 64, 128, 256
#   and 512 rows read 0.328, 0.306, 0.306 and 0.302 s for 4 M rows x 32
#   trees, PERF.md section 5; a kernel of 512 compiles for three times as
#   long, and a sweep program holds dozens);
# - the predict's leaf product is ONE bfloat16 pass: the one-hot against the
#   leaf table split into its three bfloat16 terms (hi + mid + lo is the
#   float32 value exactly), summed after, in place of six passes of
#   `Precision.HIGHEST` over a one-hot whose lower terms are zeros;
# - the body is written in chunk-wide operations, some 400 equations: every
#   `pallas_call` of every program traces and lowers its body again in every
#   process, compile cache or not, and a body unrolled slab by slab (7 000)
#   added 10 s to a warm `setup_s` of 43.

_BLK_R_CHAIN = 64     # rows a block where a call has fewer than a wide block
_BLK_R_CHAIN_WIDE = 128   # rows a block of the predict from 128 rows on
_LANE_CHUNK = 2048    # lanes a select product: (128, 2048) f32 is 1 MiB
_T_CHAIN = 32         # trees per chain kernel call (lane budget)
_MAX_SLOTS = 256      # slots a level: T_pad x 256 lanes, four chunks


def _chain_block_rows(n: int) -> int:
    """Rows a grid step of the chain predict takes for a call of ``n``
    rows: the wide block once the call fills one, else 64."""
    return _BLK_R_CHAIN_WIDE if n >= _BLK_R_CHAIN_WIDE else _BLK_R_CHAIN


def _chain_widths(depth: int, W: int):
    """Ragged per-level slot widths: level l holds ≤ min(2^l, W) live slots
    (a level can at most double the previous one's count, capped at W)."""
    return [min(2 ** level, W) for level in range(depth)]


def _chain_w_eff(Wl: int) -> int:
    """Kernel lane width per level: floored at 4 so T_pad·W_eff stays a
    128-multiple (T_pad is a multiple of 32)."""
    return max(4, Wl)


def _chain_lane_chunk(depth: int, W: int, T_pad: int = _T_CHAIN) -> int:
    """Lanes of the widest select product a block makes."""
    return min(_LANE_CHUNK,
               T_pad * _chain_w_eff(max(_chain_widths(depth, W))))


def chain_block_shape(n: int, depth: int, W: int):
    """(rows a block, lanes a chunk) the chain predict of ``n`` rows runs
    with: what `predict_span_attrs` reports."""
    return _chain_block_rows(n), _chain_lane_chunk(depth, W)


def _check_slots(W: int) -> None:
    if W > _MAX_SLOTS or W & (W - 1):
        raise ValueError(
            f"n_slots={W}: the chain kernels take a power of two up to "
            f"{_MAX_SLOTS} (T_pad x {_MAX_SLOTS} lanes a level, walked in "
            f"whole chunks and folded by halves)")


def _chain_d_pad(d: int) -> int:
    """Columns of the kernels' code block: the row's ``d`` codes and the two
    columns of ones that carry a lane's threshold and base into the select
    product."""
    return _pad_to(d + 2, 128)


def _chain_tables(feat_lv, bin_lv, base_lv, depth, W, n_bins, T_pad):
    """j-major ragged per-level tables, concatenated flat: level l occupies
    T_pad·_chain_w_eff(W_l) lanes (lane = slot·T_pad + t). Sentinel bins fill
    padded slots/trees; padded bases are 0 (no rows ever sit there).

    A lane's three numbers ride ONE select product: against a row's
    ``[codes, 1, 1, 0...]`` the lane's column holds a one at its split
    feature, ``256 - bin`` at row ``d`` and ``512·base`` at row ``d + 1``
    (each exact in bfloat16: a bin is at most 256, a base under 256), so the
    product reads ``code + 256 - bin + 512·base``. Returns the (3, Σw)
    int32 rows (feature, 256 - bin, 512·base) a block builds each chunk's
    columns from (`_select_columns`)."""
    T = feat_lv.shape[0]
    rows = [[], [], []]
    for level, Wl in enumerate(_chain_widths(depth, W)):
        We = _chain_w_eff(Wl)
        pad = ((0, T_pad - T), (0, We - Wl))
        f = jnp.pad(feat_lv[:, level, :Wl], pad)
        b = jnp.pad(bin_lv[:, level, :Wl], pad, constant_values=n_bins)
        a = jnp.pad(base_lv[:, level, :Wl], pad)
        for out, x in zip(rows, (f, 256 - b, 512 * a)):
            out.append(x.T.reshape(-1).astype(jnp.int32))
    return jnp.stack([jnp.concatenate(r) for r in rows])      # (3, Σw)


def _select_columns(t_ref, at, d: int, d_pad: int):
    """In-kernel: lanes ``at`` of the (3, Σw) table → their (d_pad, c)
    bfloat16 select columns. Only the first ``d + 2`` rows can hold
    anything, so only they (to a whole bfloat16 tile) are compared."""
    c = at.stop - at.start
    d_up = _pad_to(d + 2, 16)
    row = jax.lax.broadcasted_iota(jnp.int32, (d_up, c), 0)
    cols = jnp.where(row == d, t_ref[1:2, at],
                     jnp.where(row == d + 1, t_ref[2:3, at],
                               (row == t_ref[0:1, at]).astype(jnp.int32)))
    cols = cols.astype(jnp.float32).astype(jnp.bfloat16)
    if d_up == d_pad:
        return cols
    return jnp.concatenate(
        [cols, jnp.zeros((d_pad - d_up, c), jnp.bfloat16)], axis=0)


def _chain_codes(codes, n_pad: int):
    """(n, d) int32 bin codes → the kernels' (n_pad, d_pad) bfloat16 block
    rows ``[codes, 1, 1, 0...]`` (codes up to 256 are exact)."""
    n, d = codes.shape
    ones = jnp.ones((n, 2), jnp.bfloat16)
    return jnp.pad(
        jnp.concatenate([codes.astype(jnp.bfloat16), ones], axis=1),
        ((0, n_pad - n), (0, _chain_d_pad(d) - d - 2)))


def _lane_slots(c: int, T_pad: int):
    """(1, c) float32: the slot each lane of a level's first ``c`` lanes
    stands for (lane = slot·T_pad + t)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    return (lane // T_pad).astype(jnp.float32)


def _fold_lanes(x):
    """(R, c) → (R, 128): the sum of ``x``'s 128-lane slabs, by halves."""
    while x.shape[1] > 128:
        half = x.shape[1] // 2
        x = x[:, :half] + x[:, half:]
    return x


def _descend_chain(codes_bf, t_ref, *, d, depth, W, T_pad, chunk):
    """In-kernel: (R, d_pad) bf16 code rows → (R, 128) float32 leaf slots,
    the slot of tree t on every lane ≡ t mod T_pad.

    Per level, chunk by chunk of its lanes: ONE matmul
    (R, d_pad) x (d_pad, chunk) reads ``code + 256 - bin + 512·base`` on
    every lane (`_chain_tables`), and a compare and a select keep a lane's
    number where the row's slot is the lane's. One lane a tree matches, so
    the chain of selects over the chunks and the sum of the 128-lane slabs
    after it ARE the group-sum over a tree's slots; rolls by 64 and 32
    lanes then add the slab's four tree groups, which leaves the result
    tiled along the lanes as the next level's compare wants it.
    ``floor(q / 512)`` is the base, the rest is over 256 where the code is
    over the bin. Every value is an integer under 2^18, exact in float32, so
    the slots are `route_codes_chain_xla`'s whatever the chunk or the
    block. ``W`` is a power of two (`_check_slots`): a level's lanes are
    walked in whole chunks and folded by halves. The body is a few hundred
    equations: every `pallas_call` of every program traces and lowers it
    again in every process."""
    from jax.experimental.pallas import tpu as pltpu

    R, d_pad = codes_bf.shape
    slot = jnp.zeros((R, 128), jnp.float32)
    off = 0
    for Wl in _chain_widths(depth, W):
        w = T_pad * _chain_w_eff(Wl)
        c = min(chunk, w)
        mine = _tile_lanes(slot, c // 128)                    # (R, c)
        lanes = _lane_slots(c, T_pad)
        q = jnp.zeros((R, c), jnp.float32)
        for lo in range(0, w, c):
            at = slice(off + lo, off + lo + c)
            q_all = jnp.dot(codes_bf, _select_columns(t_ref, at, d, d_pad),
                            preferred_element_type=jnp.float32)  # (R, c)
            q = jnp.where(mine == lanes + float(lo // T_pad), q_all, q)
        off += w
        q = _fold_lanes(q)
        shift = 64
        while shift >= T_pad:
            q = q + pltpu.roll(q, shift, axis=1)
            shift //= 2
        base = jnp.floor(q * (1.0 / 512.0))
        slot = base + jnp.where(q - 512.0 * base > 256.0, 1.0, 0.0)
    return slot


def _leaf_onehot_chain(slot, lo, c, *, T_pad, dtype):
    """Lanes lo..lo+c of the (R, T_pad·W_out) leaf one-hot, lane =
    slot·T_pad + t, from the (R, 128) tiled slots."""
    return (_tile_lanes(slot, c // 128)
            == _lane_slots(c, T_pad) + float(lo // T_pad)).astype(dtype)


def _leaf_sums_chain_pallas(codes_p, tables, aug, *, d, depth, W, W_out,
                            T_pad):
    """The exact leaf statistics keep their 64-row accumulation order
    (`out_ref += part` a 64-row block): the float32 sums are the ones the
    kernel has always given, bit for bit. Only the descent is the new one."""
    from jax.experimental import pallas as pl

    n, k = aug.shape
    k_pad = _pad_to(k, 8)
    lanes_out = T_pad * _chain_w_eff(W_out)
    blk_r = _BLK_R_CHAIN
    chunk = _chain_lane_chunk(depth, W, T_pad)
    n_pad = codes_p.shape[0]
    aug_p = jnp.pad(aug.astype(jnp.float32),
                    ((0, n_pad - n), (0, k_pad - k)))

    def kernel(codes_ref, t_ref, aug_ref, out_ref):
        r = pl.program_id(0)
        slot = _descend_chain(codes_ref[:], t_ref, d=d, depth=depth, W=W,
                              T_pad=T_pad, chunk=chunk)
        l_oh = _leaf_onehot_chain(slot, 0, lanes_out, T_pad=T_pad,
                                  dtype=jnp.float32)
        part = jax.lax.dot_general(
            aug_ref[:], l_oh,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

        @pl.when(r == 0)
        def _():
            out_ref[:] = part

        @pl.when(r > 0)
        def _():
            out_ref[:] += part

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((k_pad, lanes_out), jnp.float32),
        grid=(n_pad // blk_r,),
        in_specs=[
            pl.BlockSpec((blk_r, codes_p.shape[1]), lambda r: (r, 0)),
            pl.BlockSpec(tables.shape, lambda r: (0, 0)),
            pl.BlockSpec((blk_r, k_pad), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((k_pad, lanes_out), lambda r: (0, 0)),
        interpret=_interpret(),
    )(codes_p, tables, aug_p)
    # (k, slot·T_pad + t) -> (T_pad, W_out, k)
    return out.reshape(k_pad, -1, T_pad).transpose(2, 1, 0)[:, :W_out, :k]


def _bf16_terms(x):
    """float32 ``x`` as three bfloat16 terms hi + mid + lo == x exactly:
    each keeps the eight leading bits of what the ones before left (by
    mask, so no rounding that the compiler might drop)."""
    terms = []
    for _ in range(3):
        t = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
        terms.append(t.astype(jnp.bfloat16))
        x = x - t
    return terms


def _predict_chain_pallas(codes_p, tables, leaf_flat, *, blk_r, d, depth, W,
                          W_out, T_pad):
    """(n_pad, 3k padded) float32: a row's sum over the call's trees of each
    leaf value's three bfloat16 terms, columns [hi | mid | lo]."""
    from jax.experimental import pallas as pl

    n_pad = codes_p.shape[0]
    k = leaf_flat.shape[1]
    k_pad = _pad_to(3 * k, 128)
    lanes_out = T_pad * _chain_w_eff(W_out)
    chunk = _chain_lane_chunk(depth, W, T_pad)
    # the three bfloat16 terms of every leaf value side by side: one pass
    # of the array over the one-hot, three columns a value, summed after
    leaf_p = jnp.pad(
        jnp.concatenate(_bf16_terms(leaf_flat.astype(jnp.float32)), axis=1),
        ((0, lanes_out - leaf_flat.shape[0]), (0, k_pad - 3 * k)))

    def kernel(codes_ref, t_ref, leaf_ref, out_ref):
        slot = _descend_chain(codes_ref[:], t_ref, d=d, depth=depth, W=W,
                              T_pad=T_pad, chunk=chunk)
        out = None
        for lo in range(0, lanes_out, chunk):
            c = min(chunk, lanes_out - lo)
            l_oh = _leaf_onehot_chain(slot, lo, c, T_pad=T_pad,
                                      dtype=jnp.bfloat16)
            part = jnp.dot(l_oh, leaf_ref[lo:lo + c, :],
                           preferred_element_type=jnp.float32)
            out = part if out is None else out + part
        out_ref[:] = out

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        grid=(n_pad // blk_r,),
        in_specs=[
            pl.BlockSpec((blk_r, codes_p.shape[1]), lambda r: (r, 0)),
            pl.BlockSpec(tables.shape, lambda r: (0, 0)),
            pl.BlockSpec(leaf_p.shape, lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_r, k_pad), lambda r: (r, 0)),
        interpret=_interpret(),
    )(codes_p, tables, leaf_p)


def route_codes_chain_xla(codes: jnp.ndarray, feat_lv: jnp.ndarray,
                          bin_lv: jnp.ndarray, base_lv: jnp.ndarray,
                          n_bins: int) -> jnp.ndarray:
    """(n, T) leaf-slot assignments for slot-chain trees, plain XLA."""
    n, d = codes.shape
    T, depth, W = feat_lv.shape
    codes_bf = codes.astype(jnp.bfloat16)
    slot = jnp.zeros((n, T), jnp.int32)
    for level, Wl in enumerate(_chain_widths(depth, W)):
        f_l = feat_lv[:, level, :Wl]                         # (T, Wl)
        b_l = bin_lv[:, level, :Wl]
        a_l = base_lv[:, level, :Wl]
        sel = (f_l.reshape(-1)[None, :]
               == jnp.arange(d, dtype=jnp.int32)[:, None]
               ).astype(jnp.bfloat16)                        # (d, T·Wl)
        code_sel = (codes_bf @ sel).reshape(n, T, Wl)
        go_all = code_sel > b_l[None].astype(jnp.bfloat16)
        s_oh = slot[:, :, None] == jnp.arange(Wl, dtype=jnp.int32)
        go = jnp.any(go_all & s_oh, axis=2)
        base = jnp.sum(jnp.where(s_oh, a_l[None], 0), axis=2)
        slot = base + go.astype(jnp.int32)
    return slot


def _chain_xla_rowblocks(codes, fn, blk: int = 16384):
    """Run ``fn(codes_block)`` over row blocks via lax.map — the XLA chain
    fallback's per-level (n, T·W) transients would otherwise be O(n) HBM."""
    n = codes.shape[0]
    if n <= blk:
        return fn(codes), n
    n_pad = -(-n // blk) * blk
    codes_p = jnp.pad(codes, ((0, n_pad - n), (0, 0)),
                      constant_values=-1)    # code -1: routes left everywhere
    blocks = codes_p.reshape(n_pad // blk, blk, -1)
    return jax.lax.map(fn, blocks), n


def _chain_leaf_onehot_xla(c, feat_lv, bin_lv, base_lv, W_out, n_bins):
    """Route a row block down the chain tables and expand the (rows, T·W_out)
    leaf-slot one-hot — the shared front half of the XLA leaf-sums/predict
    fallbacks."""
    T = feat_lv.shape[0]
    node = route_codes_chain_xla(c, feat_lv, bin_lv, base_lv, n_bins)
    comb = node + (jnp.arange(T, dtype=jnp.int32) * W_out)[None, :]
    return (comb[:, :, None]
            == jnp.arange(T * W_out, dtype=jnp.int32).reshape(1, T, W_out)
            ).astype(jnp.float32).reshape(c.shape[0], T * W_out)


def _leaf_sums_chain_xla(codes, feat_lv, bin_lv, base_lv, aug, *, n_bins):
    n = codes.shape[0]
    T, depth, W = feat_lv.shape
    W_out = min(2 ** depth, W)
    aug_f = aug.astype(jnp.float32)
    blk = 16384

    def one(args):
        c, a = args
        l_oh = _chain_leaf_onehot_xla(c, feat_lv, bin_lv, base_lv, W_out,
                                      n_bins)
        return jnp.einsum("na,nk->ak", l_oh, a,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    if n <= blk:
        return one((codes, aug_f)).reshape(T, W_out, -1)
    n_pad = -(-n // blk) * blk
    codes_p = jnp.pad(codes, ((0, n_pad - n), (0, 0)))
    aug_p = jnp.pad(aug_f, ((0, n_pad - n), (0, 0)))  # zero rows: no-op
    parts = jax.lax.map(one, (codes_p.reshape(-1, blk, codes.shape[1]),
                              aug_p.reshape(-1, blk, aug.shape[1])))
    return parts.sum(0).reshape(T, W_out, -1)


def _predict_chain_xla(codes, feat_lv, bin_lv, base_lv, leaf, *, n_bins):
    T, depth, W = feat_lv.shape
    W_out, k = leaf.shape[1], leaf.shape[2]
    leaf_2d = leaf.reshape(T * W_out, k).astype(jnp.float32)

    def one(c):
        l_oh = _chain_leaf_onehot_xla(c, feat_lv, bin_lv, base_lv, W_out,
                                      n_bins)
        return jnp.einsum("na,ak->nk", l_oh, leaf_2d,
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    out, n = _chain_xla_rowblocks(codes, one)
    if out.ndim == 3:
        out = out.reshape(-1, out.shape[-1])[:n]
    return out


def forest_leaf_sums_chain(codes: jnp.ndarray, feat_lv: jnp.ndarray,
                           bin_lv: jnp.ndarray, base_lv: jnp.ndarray,
                           aug: jnp.ndarray, *, n_bins: int) -> jnp.ndarray:
    """Exact leaf statistics for slot-chain trees in one fused pass.

    feat_lv/bin_lv/base_lv: (T, depth, W) per-level slot tables (level l uses
    the first min(2^l, W) slots); aug: (n, k) f32 per-row stats. Returns
    (T, W_out, k) with W_out = min(2^depth, W).
    """
    _check_bins(n_bins)
    T, depth, W = feat_lv.shape
    _check_slots(W)
    W_out = min(2 ** depth, W)
    if not _use_pallas():
        return _leaf_sums_chain_xla(codes, feat_lv, bin_lv, base_lv, aug,
                                    n_bins=n_bins)
    parts = []
    n, d = codes.shape
    codes_p = _chain_codes(codes, _pad_to(n, _BLK_R_CHAIN))
    for lo in range(0, T, _T_CHAIN):
        hi = min(lo + _T_CHAIN, T)
        T_pad = _T_CHAIN
        tables = _chain_tables(
            feat_lv[lo:hi], bin_lv[lo:hi], base_lv[lo:hi], depth, W, n_bins,
            T_pad)
        out = _leaf_sums_chain_pallas(
            codes_p, tables, aug, d=d, depth=depth, W=W, W_out=W_out,
            T_pad=T_pad)
        parts.append(out[:hi - lo])
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def forest_predict_chain(codes: jnp.ndarray, feat_lv: jnp.ndarray,
                         bin_lv: jnp.ndarray, base_lv: jnp.ndarray,
                         leaf: jnp.ndarray, *, n_bins: int) -> jnp.ndarray:
    """Σ_t leaf[t, slot(row, t), :] for slot-chain trees in one fused pass.

    leaf: (T, W_out, k) f32 leaf values. Returns (n, k) f32.
    """
    _check_bins(n_bins)
    T, depth, W = feat_lv.shape
    _check_slots(W)
    W_out, k = leaf.shape[1], leaf.shape[2]
    if not _use_pallas():
        return _predict_chain_xla(codes, feat_lv, bin_lv, base_lv, leaf,
                                  n_bins=n_bins)
    out = None
    n, d = codes.shape
    blk_r = _chain_block_rows(n)
    codes_p = _chain_codes(codes, _pad_to(n, blk_r))
    for lo in range(0, T, _T_CHAIN):
        hi = min(lo + _T_CHAIN, T)
        T_pad = _T_CHAIN
        tables = _chain_tables(
            feat_lv[lo:hi], bin_lv[lo:hi], base_lv[lo:hi], depth, W, n_bins,
            T_pad)
        leaf_flat = (jnp.pad(leaf[lo:hi].astype(jnp.float32),
                             ((0, T_pad - (hi - lo)), (0, 0), (0, 0)))
                     .transpose(1, 0, 2).reshape(T_pad * W_out, k))
        terms = _predict_chain_pallas(
            codes_p, tables, leaf_flat, blk_r=blk_r, d=d, depth=depth, W=W,
            W_out=W_out, T_pad=T_pad)
        part = (terms[:n, :k] + terms[:n, k:2 * k]
                + terms[:n, 2 * k:3 * k])                   # hi + mid + lo
        out = part if out is None else out + part
    return out


def forest_predict(codes: jnp.ndarray, feat_heap: jnp.ndarray,
                   bin_heap: jnp.ndarray, leaf: jnp.ndarray, *,
                   depth: int, n_bins: int) -> jnp.ndarray:
    """Σ_t leaf[t, node(row, t), :] for every row, in one fused pass.

    leaf: (T, L, k) f32 leaf values (any per-tree weighting baked into the
    values; zero a tree's leaves to drop it). Returns (n, k) f32.
    """
    _check_bins(n_bins)
    T, L, k = leaf.shape
    if not _pallas_ok(depth, T):
        return _predict_xla(codes, feat_heap, bin_heap, leaf,
                            depth=depth, n_bins=n_bins)
    T_pad = _t_pad(T, depth)
    fh = jnp.pad(feat_heap, ((0, T_pad - T), (0, 0)))
    bh = jnp.pad(bin_heap, ((0, T_pad - T), (0, 0)),
                 constant_values=n_bins)
    f_lvls, b_lvls = _level_tables(fh, bh, depth, n_bins, T_pad)
    # (T, L, k) -> j-major rows: lane leaf·T_pad + t
    leaf_flat = (jnp.pad(leaf.astype(jnp.float32),
                         ((0, T_pad - T), (0, 0), (0, 0)))
                 .transpose(1, 0, 2).reshape(T_pad * L, k))
    return _predict_pallas(codes, f_lvls, b_lvls, leaf_flat,
                           depth=depth, n_bins=n_bins, T_pad=T_pad)
