"""Histogram-engine kernels: fused one-hot histogram matmul for tree growth.

The inner loop of histogram tree building (models/trees.py `_grow_tree`) is

    hist[a, f*nb + b] = sum_s A[s, a] * 1[codes[s, f] == b]

i.e. a matmul of per-row statistics A (S, B) against the bin one-hot matrix
(S, d*nb). XLA has to *materialize* that one-hot in HBM — 256 MB at the
65k-row split-search sample with d=64, nb=32 — and stream it back in for
every tree level of every config in the sweep. This kernel instead reads only
the int32 bin codes (S, d) — 64x less HBM traffic — and expands the one-hot
tile-by-tile in VMEM, feeding the MXU directly (the "fuse elementwise into
matmul" pattern the XLA fusion engine cannot do across a dot operand).

Replaces the JNI/native histogram plumbing of the reference's XGBoost
dependency (reference: SURVEY §2.9, ml.dmlc:xgboost4j C++ core) with a
TPU-native kernel.

Layout notes
- In-kernel the one-hot is built *bin-major* — `oh[s, b*D + f]` — because
  Mosaic can `pltpu.repeat` along lanes but not reshape (S, d, nb) → (S,
  d*nb); the cheap bin-major → feature-major permute happens outside on the
  (B, d*nb) result.
- Grid is (B blocks, D blocks, S blocks), S innermost: each (b, d) output
  block accumulates over the whole row axis before moving on.
- vmap (RF trees, GBT classes, selector configs) flattens the batch into
  extra A columns via a custom_vmap rule — one wide kernel call per tree
  level for the entire sweep, which is exactly the MXU-friendly shape.

Pinned reduction (mesh determinism)
- The XLA contraction runs as a *K-blocked* batched einsum over row blocks
  followed by an explicit fixed-order pairwise tree-combine in f32
  (`_tree_combine`). K = TG_HIST_SHARDS (default 8) is the same whether the
  program runs on one device or with rows sharded over a mesh 'data' axis —
  per-block partials are shape-identical local work either way, and the
  cross-block combine is a pinned expression rather than an
  order-unspecified `psum`, so mesh tree sweeps are bit-identical to
  single-device ones (docs/trees.md, "Determinism").
- The combine is spelt twice, one association: on one device over static
  slices of the partials (one fused elementwise pass on the chip), under
  an engine mesh by halving the array, so that a cross-device step stays
  a sum of two operands (`_tree_combine`, `_combine_form`).
- When an engine mesh context is active (``engine.engine_mesh``), the
  blocked operands and partials carry ``with_sharding_constraint`` over the
  'data' axis so the per-block GEMMs stay shard-local.

Fallback: on non-TPU backends (CPU test mesh, virtual-device dry runs) the
same contraction runs as the blocked XLA one-hot einsum.

NOTE: `_use_pallas()` / `_interpret()` read TG_TREE_PALLAS
and the backend at *trace time* inside jitted tree fits — once a shape is
traced, flipping the env var has no effect for that shape until the jit
caches are cleared (`jax.clear_caches()`), which tests that toggle the flag
must do. The pallas path (TPU single-device) does not use the K-blocked
contraction; force TG_TREE_PALLAS=0 when bit-equality across topologies is
required (see docs/trees.md).
"""
from __future__ import annotations

import math
import os
from functools import lru_cache

import jax
import jax.numpy as jnp

_BLK_S = 1024   # rows per tile

#: beyond this many stat columns the one-hot re-expansion per column block
#: outweighs the saved HBM traffic — fall back to the XLA contraction
#: (empirically: RF's 1600-wide flattened tree batch regressed 11%)
_HIST_PALLAS_MAX_B = 1024
_BLK_B = 128    # stat columns per tile


def _use_pallas() -> bool:
    env = os.environ.get("TG_TREE_PALLAS", "")
    if env in ("0", "false"):
        return False
    if env in ("1", "true"):
        return True
    # a Mosaic custom call has no partitioning rule: inside a GSPMD program
    # XLA would gather every row onto every chip, so under an engine mesh
    # the XLA contraction (which shards) runs instead
    return jax.default_backend() == "tpu" and current_engine_mesh() is None


def _interpret() -> bool:
    """Run the kernels in pallas interpret mode off-TPU (CI coverage of the
    kernel logic itself; forced via TG_TREE_PALLAS=1 on CPU)."""
    return jax.default_backend() != "tpu"


def _hist_shards() -> int:
    """K, the pinned row-block count of the XLA contraction (TG_HIST_SHARDS,
    default 8). 0/1 disables blocking — plain single-einsum contraction,
    the pre-engine numerics."""
    try:
        k = int(os.environ.get("TG_HIST_SHARDS", "8"))
    except ValueError:
        k = 8
    return max(1, k)


def _tile_lanes(x, repeats: int):
    """``[x, x, …]`` concatenated ``repeats`` times along lanes (axis 1).

    ``pltpu.repeat`` tiles the whole vector — Mosaic's RepeatOp on the chip,
    ``jnp.tile`` in interpret mode — and every kernel lane layout here is
    built on that (NOT element-wise ``jnp.repeat`` semantics)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.repeat(x, repeats, axis=1)


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------------------
# Engine mesh context: set by the fused sweep path (validators) around the
# trace of a mesh program so the blocked contraction can pin its row blocks
# to the 'data' axis. Read at TRACE time, like _use_pallas().
# --------------------------------------------------------------------------

import contextvars as _contextvars

_ENGINE_MESH = _contextvars.ContextVar("tg_histeng_mesh", default=None)


def current_engine_mesh():
    """The mesh the histogram engine should shard row blocks over, or None."""
    return _ENGINE_MESH.get()


def _data_spec(mesh, ndim: int):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec("data", *([None] * (ndim - 1))))


def _combine_form() -> str:
    """The spelling `_tree_combine` traces (its docstring says why there
    are two): ``"fused"`` on one device, ``"halving"`` under an engine
    mesh. Read at TRACE time, like `_use_pallas()`."""
    return "fused" if current_engine_mesh() is None else "halving"


def _tree_combine(parts: jnp.ndarray) -> jnp.ndarray:
    """Fixed-order pairwise tree reduction over axis 0, exact f32 adds.

    The combine is an explicit expression — (p0+p1)+(p2+p3) … — so its
    floating-point result is pinned by construction: the same bits on one
    device and on a mesh, unlike `psum`/plain `.sum(0)` whose grouping the
    compiler may re-associate across topologies. Each round adds neighbours
    and carries an odd leftover to the next; the two spellings below build
    that one association and differ only in what the chip's compiler makes
    of them (`_combine_form`):

    - on one device, over the static slices ``parts[i]``: the whole tree is
      ONE elementwise fusion that reads the K partials once and writes the
      result once (strided slices, ``parts[0::2] + parts[1::2]``, lowered
      to update loops: 64 % of the boosted-trees sweep fit, PERF.md PR 31);
    - under an engine mesh, by halving the array itself (reshape to
      ``(h, 2, …)``, add the two static columns): a round whose pairs lie
      on different devices compiles to an all-reduce over PAIRS of devices,
      a sum of two terms that no order can change. The closed form over
      slices becomes local sums and ONE all-reduce over the whole 'data'
      axis there, whose order the hardware chooses: the pinned property
      would be gone (tests/test_device_names_tpu.py holds both)."""
    if _combine_form() == "fused":
        terms = [parts[i] for i in range(parts.shape[0])]
        while len(terms) > 1:
            h = len(terms) // 2
            terms = ([terms[2 * i] + terms[2 * i + 1] for i in range(h)]
                     + terms[2 * h:])
        return terms[0]
    while parts.shape[0] > 1:
        h = parts.shape[0] // 2
        pairs = parts[:2 * h].reshape(h, 2, *parts.shape[1:])
        s = pairs[:, 0] + pairs[:, 1]
        if parts.shape[0] % 2:
            s = jnp.concatenate([s, parts[2 * h:]], axis=0)
        parts = s
    return parts[0]


def _hist_xla(codes: jnp.ndarray, A: jnp.ndarray, n_bins: int,
              exact: bool = False) -> jnp.ndarray:
    """Reference contraction, feature-major (B, d*nb) f32 — single einsum,
    no row blocking (used when TG_HIST_SHARDS<=1 or S<K)."""
    S, d = codes.shape
    dt = jnp.float32 if exact else jnp.bfloat16
    oh = (codes[:, :, None] == jnp.arange(n_bins, dtype=jnp.int32)
          ).astype(dt).reshape(S, d * n_bins)
    # materialize the one-hot: left fusible, XLA lowers the contraction as a
    # pred-kernel convolution in some surrounding graphs (~6x slower than
    # the plain einsum on v5e — seen in the tree grower's level loop)
    oh = jax.lax.optimization_barrier(oh)
    kw = ({"precision": jax.lax.Precision.HIGHEST} if exact else {})
    return jnp.einsum("sa,sf->af", A.astype(dt), oh,
                      preferred_element_type=jnp.float32, **kw)


def _hist_xla_pinned(codes: jnp.ndarray, A: jnp.ndarray, n_bins: int,
                     exact: bool = False) -> jnp.ndarray:
    """K-blocked contraction with pinned combine order (see module notes).

    Rows are sentinel-padded to a multiple of K (code == n_bins matches no
    one-hot lane; the padded stat rows are zero), reshaped to (K, S/K, ·),
    contracted as one batched einsum into per-block f32 partials, and
    combined by `_tree_combine`. Under an active engine mesh context the
    blocked axes carry sharding constraints over 'data'."""
    K = _hist_shards()
    S, d = codes.shape
    if K <= 1 or S < K:
        return _hist_xla(codes, A, n_bins, exact)
    B = A.shape[1]
    Sp = _pad_to(S, K)
    codes_p = jnp.pad(codes.astype(jnp.int32), ((0, Sp - S), (0, 0)),
                      constant_values=n_bins)
    A_p = jnp.pad(A, ((0, Sp - S), (0, 0)))
    cb = codes_p.reshape(K, Sp // K, d)
    ab = A_p.reshape(K, Sp // K, B)
    mesh = current_engine_mesh()
    if mesh is not None:
        cb = jax.lax.with_sharding_constraint(cb, _data_spec(mesh, 3))
        ab = jax.lax.with_sharding_constraint(ab, _data_spec(mesh, 3))
    dt = jnp.float32 if exact else jnp.bfloat16
    oh = (cb[:, :, :, None] == jnp.arange(n_bins, dtype=jnp.int32)
          ).astype(dt).reshape(K, Sp // K, d * n_bins)
    oh = jax.lax.optimization_barrier(oh)
    kw = ({"precision": jax.lax.Precision.HIGHEST} if exact else {})
    parts = jnp.einsum("ksa,ksf->kaf", ab.astype(dt), oh,
                       preferred_element_type=jnp.float32, **kw)
    if mesh is not None:
        parts = jax.lax.with_sharding_constraint(parts, _data_spec(mesh, 3))
    return _tree_combine(parts)


def pinned_row_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Fixed-order K-blocked sum over ``axis`` (rows), bit-identical across
    mesh topologies — the non-histogram companion to `_hist_xla_pinned` for
    the few direct row reductions in tree fits (GBT's base-score f0)."""
    K = _hist_shards()
    x = jnp.moveaxis(x, axis, 0)
    S = x.shape[0]
    if K <= 1 or S < K:
        return x.sum(0)
    Sp = _pad_to(S, K)
    xp = jnp.pad(x, ((0, Sp - S),) + ((0, 0),) * (x.ndim - 1))
    xb = xp.reshape(K, Sp // K, *x.shape[1:])
    mesh = current_engine_mesh()
    if mesh is not None:
        xb = jax.lax.with_sharding_constraint(xb, _data_spec(mesh, xb.ndim))
    parts = xb.sum(1)
    if mesh is not None:
        xb_spec = _data_spec(mesh, parts.ndim)
        parts = jax.lax.with_sharding_constraint(parts, xb_spec)
    return _tree_combine(parts)


def _hist_pallas(codes: jnp.ndarray, A: jnp.ndarray,
                 n_bins: int, exact: bool = False) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, d = codes.shape
    B = A.shape[1]
    # feature blocking: either one full-width block (any lane count whose
    # nb*d_pad is a multiple of 128) or 128-wide feature tiles — Mosaic
    # requires block dims be 128-divisible or span the whole array axis
    d_mult = 128 // math.gcd(n_bins, 128)
    d_pad = _pad_to(d, d_mult)
    if d_pad > 128:
        d_pad = _pad_to(d_pad, 128)
        blk_d = 128
    else:
        blk_d = d_pad
    lanes = n_bins * blk_d
    # keep the VMEM one-hot tile (blk_s × lanes bf16) around ≤4 MB
    blk_s = _BLK_S
    while blk_s > 256 and blk_s * lanes * 2 > (4 << 20):
        blk_s //= 2
    s_pad = _pad_to(S, blk_s)
    b_pad = _pad_to(B, 8)
    blk_b = min(_BLK_B, b_pad)
    if b_pad > _BLK_B:
        b_pad = _pad_to(b_pad, _BLK_B)

    # sentinel bin n_bins never matches a one-hot lane → padded rows/features
    # contribute exact zeros
    codes_p = jnp.pad(codes.astype(jnp.int32),
                      ((0, s_pad - S), (0, d_pad - d)),
                      constant_values=n_bins)
    A_p = jnp.pad(A.astype(jnp.float32), ((0, s_pad - S), (0, b_pad - B)))

    def kernel(codes_ref, a_ref, out_ref):
        s = pl.program_id(2)
        rep = _tile_lanes(codes_ref[:], n_bins)             # (blk_s, nb*blk_d)
        b_iota = (jax.lax.broadcasted_iota(jnp.int32, (blk_s, lanes), 1)
                  // blk_d)
        if exact:
            # f32 stat operands, HIGHEST precision: leaf-value reductions
            # (served predictions) must not round to bf16
            oh = (rep == b_iota).astype(jnp.float32)
            part = jnp.dot(a_ref[:].T, oh,
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
        else:
            oh = (rep == b_iota).astype(jnp.bfloat16)
            part = jnp.dot(a_ref[:].T.astype(jnp.bfloat16), oh,
                           preferred_element_type=jnp.float32)

        @pl.when(s == 0)
        def _():
            out_ref[:] = part

        @pl.when(s > 0)
        def _():
            out_ref[:] += part

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b_pad, d_pad * n_bins), jnp.float32),
        grid=(b_pad // blk_b, d_pad // blk_d, s_pad // blk_s),
        in_specs=[
            pl.BlockSpec((blk_s, blk_d), lambda b, f, s: (s, f)),
            pl.BlockSpec((blk_s, blk_b), lambda b, f, s: (s, b)),
        ],
        out_specs=pl.BlockSpec((blk_b, lanes), lambda b, f, s: (b, f)),
        interpret=_interpret(),
    )(codes_p, A_p)

    # bin-major blocks → feature-major flat, then strip padding
    nbd = d_pad // blk_d
    out = (out.reshape(b_pad, nbd, n_bins, blk_d)
           .transpose(0, 1, 3, 2)
           .reshape(b_pad, d_pad * n_bins))
    return out[:B, :d * n_bins]


@lru_cache(maxsize=None)
def _make(n_bins: int, exact: bool = False):
    from jax.custom_batching import custom_vmap

    @custom_vmap
    def hist(codes, A):
        if _use_pallas() and A.shape[1] <= _HIST_PALLAS_MAX_B:
            return _hist_pallas(codes, A, n_bins, exact)
        return _hist_xla_pinned(codes, A, n_bins, exact)

    @hist.def_vmap
    def _rule(axis_size, in_batched, codes, A):
        codes_b, A_b = in_batched
        if codes_b:
            # not a shape this framework produces (codes are shared across
            # the sweep); keep semantics anyway
            out = jax.lax.map(lambda ca: hist(ca[0], ca[1]), (codes, A))
            return out, True
        S, B = A.shape[1], A.shape[2]
        flat = A.transpose(1, 0, 2).reshape(S, axis_size * B)
        out = hist(codes, flat)                     # (V*B, d*nb)
        return out.reshape(axis_size, B, -1), True

    return hist


@jax.named_scope("hist.build")
def hist_matmul(codes: jnp.ndarray, A: jnp.ndarray,
                n_bins: int, exact: bool = False) -> jnp.ndarray:
    """hist[a, f*n_bins + b] = Σ_s A[s, a]·1[codes[s, f] == b], f32.

    codes: (S, d) int bin indices in [0, n_bins); values == n_bins are
    allowed and contribute nothing (sentinel). A: (S, B) per-row statistics.
    Returns (B, d*n_bins) feature-major. Batches over leading axes of A
    (vmap) by widening B — the whole sweep becomes one kernel call.
    ``exact``: keep the stat operands f32 at HIGHEST precision (leaf-value
    reductions — served predictions must not round to bf16); growth
    histograms use the default bf16 operands by design.
    """
    return _make(n_bins, exact)(codes, A)


# ---------------------------------------------------------------------------
# Fused node-histogram: hist over (stat, slot, tree) lanes WITHOUT ever
# materializing the (S, k·Wl·T) masked-stat operand in HBM
# ---------------------------------------------------------------------------



def tree_lane_shape(T: int, Wl: int = 1):
    """``(T_pad, Wl_eff)``: the tree lanes and slots the shared-codes node
    histogram lays out for ``T`` trees of ``Wl`` slots. Up to 32 trees the
    tree lanes follow the count (the next power of two: 1, 2, 4 ... 32), then
    64, then multiples of 128; the slots are rounded up so that
    ``Wl_eff * T_pad`` is a multiple of 128 and every minor dimension of the
    masked-stat operand stays 128-aligned. `node_hist_matmul` lays its operand
    out by this rule, `models/trees.py` takes its leaf-sum block and the
    spans' ``treeLanesPadded`` from it."""
    if T <= 32:
        T_pad = 1 << max(T - 1, 0).bit_length()
    elif T <= 64:
        T_pad = 64
    else:
        T_pad = _pad_to(T, 128)
    rep = max(1, 128 // T_pad)
    return T_pad, _pad_to(max(Wl, rep), rep)


def _node_hist_xla(codes, node, sws, Wl_eff, n_bins, stride, k, exact=False):
    """Reference semantics: materialize the masked-stat operand and reuse the
    blocked hist contraction. node: (S, T_pad) int32 (pad -1); sws:
    (k, S, T_pad) stat-stacked. Returns (k·Wl_eff·T_pad, d·nb)."""
    S, T_pad = node.shape
    j = stride * jnp.arange(Wl_eff, dtype=jnp.int32)
    if T_pad == 1:
        # one tree: the lane is the slot. No (S, Wl_eff, 1) array is made,
        # whose minor axis of one tree the chip would lay on 128 lanes
        n_oh = (node == j[None, :]).astype(sws.dtype)         # (S, Wl_eff)
        A = jnp.concatenate([n_oh * sws[ki] for ki in range(k)], axis=1)
    else:
        n_oh = (node[:, None, :] == j[None, :, None]
                ).astype(sws.dtype)                       # (S, Wl_eff, T_pad)
        A = jnp.concatenate(
            [n_oh * sws[ki][:, None, :] for ki in range(k)],
            axis=1).reshape(S, k * Wl_eff * T_pad)
    return _hist_xla_pinned(codes, A, n_bins, exact)


def _node_hist_xla_per_tree(codes, node, sw_list, Wl, n_bins, stride):
    """The node histogram where every tree has its OWN columns: codes
    (S, T, d_sub), node (S, T), k stats (S, T). One contraction batched over
    trees, (T; S, k·Wl) x (T; S, d_sub·nb), in the pinned form of
    `_hist_xla_pinned`: the same K row blocks (sentinel-padded), the same
    'data'-axis constraints under an engine mesh, the same `_tree_combine`.
    Returns (k·Wl·T, d_sub·nb), lane = (k·Wl + j)·T + t."""
    S, T, dw = codes.shape
    k = len(sw_list)
    K = _hist_shards()
    if K <= 1 or S < K:
        K = 1
    j = stride * jnp.arange(Wl, dtype=jnp.int32)
    n_oh = (node[:, :, None] == j).astype(jnp.float32)        # (S, T, Wl)
    A = jnp.concatenate(
        [n_oh * sw.astype(jnp.float32)[:, :, None] for sw in sw_list],
        axis=2)                                               # (S, T, k·Wl)
    Sp = _pad_to(S, K)
    cb = jnp.pad(codes.astype(jnp.int32), ((0, Sp - S), (0, 0), (0, 0)),
                 constant_values=n_bins).reshape(K, Sp // K, T, dw)
    ab = jnp.pad(A, ((0, Sp - S), (0, 0), (0, 0))
                 ).reshape(K, Sp // K, T, k * Wl)
    mesh = current_engine_mesh()
    if mesh is not None:
        cb = jax.lax.with_sharding_constraint(cb, _data_spec(mesh, 4))
        ab = jax.lax.with_sharding_constraint(ab, _data_spec(mesh, 4))
    oh = (cb[..., None] == jnp.arange(n_bins, dtype=jnp.int32)
          ).astype(jnp.bfloat16).reshape(K, Sp // K, T, dw * n_bins)
    oh = jax.lax.optimization_barrier(oh)
    parts = jnp.einsum("rsta,rstf->rtaf", ab.astype(jnp.bfloat16), oh,
                       preferred_element_type=jnp.float32)
    if mesh is not None:
        parts = jax.lax.with_sharding_constraint(parts, _data_spec(mesh, 4))
    out = _tree_combine(parts)                                # (T, k·Wl, ·)
    return out.transpose(1, 0, 2).reshape(k * Wl * T, dw * n_bins)


@jax.named_scope("hist.build")
def node_hist_matmul(codes: jnp.ndarray, node: jnp.ndarray,
                     sw_list, Wl: int, n_bins: int,
                     stride: int = 1) -> jnp.ndarray:
    """hist[(k, j, t), f·nb + b] = Σ_s sw_k[s,t] · 1[node[s,t] == stride·j]
    · 1[codes[s,f] == b] — the tree-growth histogram as one XLA contraction
    over the masked-stat operand (the (S, k·Wl·T) A_cat is materialized;
    a pallas kernel that expanded it tile-by-tile in VMEM measured SLOWER
    at every production shape, sweep and refit alike, and was retired).

    codes: (S, d) int32 bin codes shared by every tree, or (S, T, d_sub)
    where each tree brings its own columns (a forest's drawn subsets: the
    contraction is then batched over trees and d_sub wide, not d); node:
    (S, T) int32 current slot per tree (values < 0 never match); sw_list: k
    arrays (S, T) of per-tree stats; ``stride``: slot-id multiplier (2 =
    heap left-children, 1 = chain slots).
    Returns (k·Wl·T, d·n_bins) f32, lane = (k·Wl + j)·T + t — identical
    layout to ``hist_matmul(codes, A_cat, n_bins)`` with A_cat built k-major
    then j-major.
    """
    if codes.ndim == 3:
        return _node_hist_xla_per_tree(codes, node, sw_list, Wl, n_bins,
                                       stride)
    S, d = codes.shape
    T = node.shape[1]
    k = len(sw_list)
    # the tree lanes are padded on purpose, and the slots with them, so that
    # every minor dimension is 128-aligned. MEASURED on v5e at T = 54 (the
    # default grid's boosted sweep): laid on 64 lanes ~108 fits/sec, ragged
    # on 54 ~88 (the A_cat expansion and the contraction tile better,
    # logical-FLOP savings notwithstanding). At T = 1 (a boosted winner's
    # refit, one tree a round at 65 536 rows x 28 columns, 256 slots) a
    # block of 32 lanes was 31 lanes of zeros: 18.8 ms a level, 0.8 ms on
    # one lane (PERF.md, PR 43). So the padding follows T up to 32 and is
    # what it was above: `tree_lane_shape`
    T_pad, Wl_eff = tree_lane_shape(T, Wl)
    node_p = (jnp.pad(node, ((0, 0), (0, T_pad - T)), constant_values=-1)
              if T_pad != T else node)
    sws = jnp.stack(
        [jnp.pad(sw.astype(jnp.float32), ((0, 0), (0, T_pad - T)))
         if T_pad != T else sw.astype(jnp.float32) for sw in sw_list])
    out = _node_hist_xla(codes, node_p, sws, Wl_eff, n_bins, stride, k)
    if Wl_eff != Wl or T_pad != T:
        out = (out.reshape(k, Wl_eff, T_pad, d * n_bins)[:, :Wl, :T]
               .reshape(k * Wl * T, d * n_bins))
    return out


# Routing no longer lives here: the per-level decision-bit contraction
# (route_matmul) was replaced by the feature-select matmul inside
# models/trees.py _grow_tree (1/n_bins-th the FLOPs) and by the fused
# multi-level descent kernel in ops/forest.py for full-data passes.
