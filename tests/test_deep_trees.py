"""Slot-chain ("leaf budget") deep trees: the depth-12 path of the default
grids (reference DefaultSelectorParams.scala:37 sweeps maxDepth {3, 6, 12};
a complete heap caps out near depth 8, so deeper trees grow level-wise with
a gain-ranked frontier of n_slots leaves — VERDICT r3 missing #1)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from transmogrifai_tpu.models.api import MODEL_REGISTRY
from transmogrifai_tpu.models import trees as T
from transmogrifai_tpu.ops.forest import (
    forest_predict_chain, forest_leaf_sums_chain, route_codes_chain_xla,
    route_codes_xla,
)


def _binary_data(n=600, d=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0.5)).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _acc(scores, y):
    return ((np.asarray(scores) > 0.5).astype(int)
            == np.asarray(y)).mean()


def _fit(fam_name, grid, X, y, num_classes=2, sweep=False):
    fam = MODEL_REGISTRY[fam_name]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((len(grid), X.shape[0]), jnp.float32)
    return fam, fam.fit_batch(X, y, w, garr, num_classes, sweep=sweep)


# ---------------------------------------------------------------------------
# Chain layout is an exact re-expression of complete heaps
# ---------------------------------------------------------------------------

def test_heap_embedding_routes_identically():
    """A depth-3 heap converted via _heap_to_chain at depth 12 must route
    every row to the same leaf id the heap descent computes."""
    rng = np.random.RandomState(3)
    n_bins, d, Tn, dh = 32, 6, 4, 3
    codes = jnp.asarray(rng.randint(0, n_bins, size=(300, d), dtype=np.int32))
    H = 2 ** dh - 1
    feat = jnp.asarray(rng.randint(0, d, size=(Tn, H), dtype=np.int32))
    bins = jnp.asarray(rng.randint(0, n_bins - 1, size=(Tn, H),
                                   dtype=np.int32))
    # stop some nodes (sentinel) to exercise the route-left semantics
    bins = bins.at[:, 4].set(n_bins)
    leaf = jnp.asarray(rng.randn(Tn, 2 ** dh, 2).astype(np.float32))
    params = {"feat": feat, "bins": bins,
              "thresh": jnp.zeros((Tn, H), jnp.float32), "leaf": leaf}
    chain = T._heap_to_chain(params, dh, 12, 64, n_bins, leaf_axis=-2)
    node_heap = np.asarray(route_codes_xla(codes, feat, bins, dh, n_bins))
    node_chain = np.asarray(route_codes_chain_xla(
        codes, chain["feat_lv"], chain["bins_lv"], chain["base_lv"], n_bins))
    np.testing.assert_array_equal(node_heap, node_chain)
    # and the chain predict returns exactly the heap-selected leaf values
    pred = np.asarray(forest_predict_chain(
        codes, chain["feat_lv"], chain["bins_lv"], chain["base_lv"],
        chain["leaf"], n_bins=n_bins))
    expect = np.asarray(leaf)[np.arange(Tn)[None, :], node_heap].sum(1)
    np.testing.assert_allclose(pred, expect, rtol=1e-6)


def _random_chain(rng, n_bins, d, Tn, depth, W):
    """Random but CONSISTENT chain: base pointers within next level's width,
    some slots leaves (sentinel bin)."""
    feat = rng.randint(0, d, size=(Tn, depth, W)).astype(np.int32)
    bins_ = rng.randint(0, n_bins - 1, size=(Tn, depth, W)).astype(np.int32)
    base = np.zeros((Tn, depth, W), np.int32)
    for lv in range(depth):
        Wl = min(2 ** lv, W)
        Wn = min(2 ** (lv + 1), W)
        base[:, lv, :Wl] = rng.randint(0, max(Wn - 1, 1), size=(Tn, Wl))
        stop = rng.rand(Tn, Wl) < 0.3
        bins_[:, lv, :Wl] = np.where(stop, n_bins, bins_[:, lv, :Wl])
    return jnp.asarray(feat), jnp.asarray(bins_), jnp.asarray(base)


def _python_slots(codes, feat, bins_, base):
    """Ground truth by per-row python descent."""
    cn = np.asarray(codes)
    fn_, bn, an = np.asarray(feat), np.asarray(bins_), np.asarray(base)
    n, (Tn, depth, _) = cn.shape[0], fn_.shape
    slots = np.zeros((n, Tn), np.int64)
    for lv in range(depth):
        for t in range(Tn):
            s = slots[:, t]
            go = cn[np.arange(n), fn_[t, lv, s]] > bn[t, lv, s]
            slots[:, t] = an[t, lv, s] + go
    return slots


# rows on both sides of the kernels' block boundaries: under one 64-row
# block (the predict's below 128 rows, the leaf sums' always), and the
# predict's 128-row block with a ragged last one after 3, 8 and 17 steps
@pytest.mark.parametrize("W", [32, 256])
@pytest.mark.parametrize("n", [63, 257, 1000, 2049])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_chain_kernels_match_xla(use_pallas, n, W, monkeypatch):
    """Pallas chain descent (interpret mode on CPU) == the XLA fallback, for
    predict and leaf sums."""
    monkeypatch.setenv("TG_TREE_PALLAS", "1" if use_pallas else "0")
    jax.clear_caches()
    rng = np.random.RandomState(7)
    n_bins, d, Tn, depth = 16, 5, 3, 10
    codes = jnp.asarray(rng.randint(0, n_bins, size=(n, d), dtype=np.int32))
    feat, bins_, base = _random_chain(rng, n_bins, d, Tn, depth, W)
    W_out = min(2 ** depth, W)
    leaf = jnp.asarray(rng.randn(Tn, W_out, 3).astype(np.float32))
    aug = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    pred = np.asarray(forest_predict_chain(codes, feat, bins_, base, leaf,
                                           n_bins=n_bins))
    sums = np.asarray(forest_leaf_sums_chain(codes, feat, bins_, base, aug,
                                             n_bins=n_bins))
    slots = _python_slots(codes, feat, bins_, base)
    expect_pred = np.asarray(leaf)[np.arange(Tn)[None, :], slots].sum(1)
    np.testing.assert_allclose(pred, expect_pred, rtol=1e-5, atol=1e-5)
    expect_sums = np.zeros((Tn, W_out, 3), np.float32)
    for t in range(Tn):
        np.add.at(expect_sums[t], slots[:, t], np.asarray(aug))
    np.testing.assert_allclose(sums, expect_sums, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,W,Tn,depth,d", [
    (63, 32, 35, 12, 7), (300, 256, 3, 9, 7),
    (1100, 32, 35, 12, 7), (1100, 32, 3, 12, 126), (200, 64, 3, 8, 130)])
def test_chain_kernel_slots_are_the_xla_routes(n, W, Tn, depth, d,
                                               monkeypatch):
    """The kernel's slots are integers, and `route_codes_chain_xla`'s
    exactly, whatever the block (64 rows or the wide one), the lane chunk
    (a 256-slot level is four), the tree chunk (35 trees: two calls) and
    the width of the select columns a block builds (126 codes and their two
    ones fill one 128-row tile, 130 need a second). They are read through a
    leaf table that holds each slot's own number in its tree's column."""
    monkeypatch.setenv("TG_TREE_PALLAS", "1")
    jax.clear_caches()
    rng = np.random.RandomState(11)
    n_bins = 32
    codes = jnp.asarray(rng.randint(0, n_bins, size=(n, d), dtype=np.int32))
    feat, bins_, base = _random_chain(rng, n_bins, d, Tn, depth, W)
    W_out = min(2 ** depth, W)
    reads_slot = (jnp.arange(W_out, dtype=jnp.float32)[None, :, None]
                  * jnp.eye(Tn, dtype=jnp.float32)[:, None, :])
    got = np.asarray(forest_predict_chain(codes, feat, bins_, base,
                                          reads_slot, n_bins=n_bins))
    want = np.asarray(route_codes_chain_xla(codes, feat, bins_, base, n_bins))
    np.testing.assert_array_equal(got, want.astype(np.float32))
    np.testing.assert_array_equal(want,
                                  _python_slots(codes, feat, bins_, base))
    jax.clear_caches()


@pytest.mark.parametrize("W", [48, 100, 512])
def test_chain_kernels_refuse_a_slot_count_they_cannot_walk(W):
    """A level's lanes are walked in whole chunks and folded by halves:
    the slot count is a power of two, at most 256."""
    z = jnp.zeros((2, 4, W), jnp.int32)
    codes = jnp.zeros((8, 3), jnp.int32)
    with pytest.raises(ValueError, match="power of two"):
        forest_predict_chain(codes, z, z, z, jnp.zeros((2, 16, 1)), n_bins=8)
    with pytest.raises(ValueError, match="power of two"):
        forest_leaf_sums_chain(codes, z, z, z, jnp.zeros((8, 1)), n_bins=8)


def test_chain_leaf_sums_keep_their_digits(monkeypatch):
    """`forest_leaf_sums_chain` accumulates a 64-row block at a time in the
    order it always has: its float32 sums on a fixed seed are, bit for bit,
    the ones the kernel gave before its descent was rewritten (PR 42; the
    digest is of the parent commit's output, interpret mode on the CPU)."""
    import hashlib
    monkeypatch.setenv("TG_TREE_PALLAS", "1")
    jax.clear_caches()
    rng = np.random.RandomState(42)
    n_bins, d, Tn, depth, W, n = 16, 5, 35, 10, 32, 300
    feat, bins_, base = _random_chain(rng, n_bins, d, Tn, depth, W)
    codes = jnp.asarray(rng.randint(0, n_bins, size=(n, d), dtype=np.int32))
    aug = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    sums = np.asarray(forest_leaf_sums_chain(codes, feat, bins_, base, aug,
                                             n_bins=n_bins))
    assert sums.shape == (35, 32, 3) and sums.dtype == np.float32
    assert [float(x).hex() for x in sums[0, 0]] == [
        "-0x1.bd42560000000p+2", "0x1.876d780000000p+1",
        "-0x1.8ddabe0000000p+2"]
    assert hashlib.sha256(np.ascontiguousarray(sums).tobytes()).hexdigest() \
        == "89808688b3ce75d74297721500bb1c8319cad8113be5f88fa73f721ed84d206e"
    jax.clear_caches()


def _kernel_blocks(fn, *args):
    """(rows a block, widest select product's lanes) of every chain kernel
    in the traced program ``fn(*args)``: the code block's shape, and the
    widest product whose left operand is that block."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                block = tuple(
                    int(getattr(b, "block_size", b)) for b in
                    eqn.params["grid_mapping"].block_mappings[0].block_shape)
                chunk = max(e.outvars[0].aval.shape[1]
                            for e in eqn.params["jaxpr"].eqns
                            if e.primitive.name == "dot_general"
                            and e.invars[0].aval.shape == block)
                found.append((block[0], int(chunk)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("rows,block", [(100, 64), (127, 64), (128, 128),
                                        (5000, 128)])
def test_predict_span_attrs_say_the_block_the_program_runs(rows, block,
                                                           monkeypatch):
    """``blockRows`` / ``laneChunk`` on the predict spans are what the
    traced predict program's kernels take a grid step: 64 rows under one
    wide block (the mechanism did not engage), the wide block from it on."""
    from transmogrifai_tpu.models.api import FittedParams
    from transmogrifai_tpu.ops import forest as F
    monkeypatch.setenv("TG_TREE_PALLAS", "1")
    jax.clear_caches()
    X, y = _binary_data(n=200)
    grid = [{"maxDepth": 12, "minInstancesPerNode": 5, "minInfoGain": 0.001,
             "maxIter": 3, "stepSize": 0.3}]
    fam, params = _fit("OpGBTClassifier", grid, X, y)
    fitted = FittedParams(family=fam.name, params=fam.select_params(params, 0),
                          hyper=grid[0], num_classes=2)
    said = fam.predict_span_attrs(fitted, rows=rows)
    assert said["blockRows"] == block
    assert said["laneChunk"] == min(F._LANE_CHUNK, 32 * T._REFIT_SLOTS)
    assert said["treeChunks"] == 1 and said["depth"] == 12
    Xr = jnp.zeros((rows, X.shape[1]), jnp.float32)
    kernels = _kernel_blocks(lambda x: fam.predict_batch(params, x, 2), Xr)
    assert kernels == [(said["blockRows"], said["laneChunk"])]
    jax.clear_caches()


# ---------------------------------------------------------------------------
# Capped grower
# ---------------------------------------------------------------------------

def test_capped_grower_matches_heap_when_uncapped():
    """With n_slots ≥ 2^depth the cap never binds: the capped grower must
    find the same trees (checked via predictions) as the heap grower."""
    X, y = _binary_data()
    fam = MODEL_REGISTRY["OpDecisionTreeClassifier"]
    grid = [{"maxDepth": 3, "minInstancesPerNode": 5, "minInfoGain": 0.001}]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((1, X.shape[0]), jnp.float32)
    p_heap = T._fit_dt_batch(
        X, y, w, garr["maxDepth"], garr["minInstancesPerNode"],
        garr["minInfoGain"], depth=3, n_bins=T.N_BINS, num_classes=2,
        task="classification")
    p_chain = T._fit_dt_batch(
        X, y, w, garr["maxDepth"], garr["minInstancesPerNode"],
        garr["minInfoGain"], depth=3, n_bins=T.N_BINS, num_classes=2,
        task="classification", n_slots=8)
    s_heap = fam.predict_batch(p_heap, X, 2)
    s_chain = fam.predict_batch(p_chain, X, 2)
    np.testing.assert_allclose(np.asarray(s_heap), np.asarray(s_chain),
                               atol=1e-5)


def test_leaf_budget_caps_leaf_count():
    """depth 12 with a tiny budget: the final sample slots stay within the
    budget and the tree still learns."""
    X, y = _binary_data(n=800)
    fam = MODEL_REGISTRY["OpDecisionTreeClassifier"]
    grid = [{"maxDepth": 12, "minInstancesPerNode": 2, "minInfoGain": 1e-4}]
    garr = fam.grid_to_arrays(grid)
    w = jnp.ones((1, X.shape[0]), jnp.float32)
    params = fam.fit_batch(X, y, w, garr, 2)
    assert "base_lv" in params
    assert params["feat_lv"].shape[-2:] == (12, T._REFIT_SLOTS)
    scores = fam.predict_batch(params, X, 2)
    assert _acc(scores[0], y) > 0.9


@pytest.mark.parametrize("fam_name,extra", [
    ("OpDecisionTreeClassifier", {}),
    ("OpRandomForestClassifier", {"numTrees": 10, "subsamplingRate": 1.0}),
    ("OpGBTClassifier", {"maxIter": 10, "stepSize": 0.3}),
])
def test_depth12_learns_binary(fam_name, extra):
    X, y = _binary_data()
    grid = [{"maxDepth": 12, "minInstancesPerNode": 5, "minInfoGain": 0.001,
             **extra}]
    fam, params = _fit(fam_name, grid, X, y)
    scores = fam.predict_batch(params, X, 2)
    acc = _acc(scores[0], y)
    assert acc > 0.9, f"{fam_name} depth-12 accuracy {acc}"


@pytest.mark.parametrize("fam_name,extra,leaf_axis", [
    ("OpDecisionTreeClassifier", {}, -2),
    ("OpRandomForestClassifier", {"numTrees": 8, "subsamplingRate": 1.0}, -2),
    ("OpGBTClassifier", {"maxIter": 6, "stepSize": 0.3}, -1),
])
def test_mixed_depth_grid_stitches_exactly(fam_name, extra, leaf_axis):
    """In a (3, 12) grid the shallow config rides the heap grower and is
    converted to the chain layout — its predictions must match a pure
    shallow fit."""
    X, y = _binary_data()
    shallow = {"maxDepth": 3, "minInstancesPerNode": 5, "minInfoGain": 0.001,
               **extra}
    deep = dict(shallow, maxDepth=12)
    fam, p_mixed = _fit(fam_name, [shallow, deep], X, y)
    assert "base_lv" in p_mixed
    _, p_shallow = _fit(fam_name, [shallow], X, y)
    s_mixed = np.asarray(fam.predict_batch(p_mixed, X, 2))
    s_shallow = np.asarray(fam.predict_batch(p_shallow, X, 2))
    np.testing.assert_allclose(s_mixed[0], s_shallow[0], atol=2e-4)
    # the deep config learns at least as well as chance
    assert _acc(s_mixed[1], y) > 0.85


def test_sweep_mode_deep_trees():
    """sweep=True deep fits use the sweep leaf budget and score validation
    rows sanely (validator contract)."""
    X, y = _binary_data()
    grid = [{"maxDepth": 12, "minInstancesPerNode": 5, "minInfoGain": 0.001,
             "numTrees": 8, "subsamplingRate": 1.0}]
    fam, params = _fit("OpRandomForestClassifier", grid, X, y, sweep=True)
    assert params["feat_lv"].shape[-1] == T._SWEEP_SLOTS
    scores = fam.predict_batch(params, X, 2)
    assert _acc(scores[0], y) > 0.85


def test_depth8_mixes_with_deep():
    """A heap bucket at depth 7-8 has more leaves than the sweep budget;
    the shared chain width must grow to hold it (review r4 finding)."""
    X, y = _binary_data(n=300)
    grid = [{"maxDepth": 8, "minInstancesPerNode": 5, "minInfoGain": 0.001},
            {"maxDepth": 12, "minInstancesPerNode": 5, "minInfoGain": 0.001}]
    fam, params = _fit("OpDecisionTreeClassifier", grid, X, y, sweep=True)
    assert params["feat_lv"].shape[-1] >= 256
    scores = fam.predict_batch(params, X, 2)
    assert scores.shape == (2, X.shape[0])


def test_chain_feature_importances():
    """Deep (slot-chain) winners still surface split-frequency importances,
    and sentinel entries do not count toward feature 0."""
    from transmogrifai_tpu.models.api import FittedParams
    X, y = _binary_data()
    grid = [{"maxDepth": 12, "minInstancesPerNode": 5, "minInfoGain": 0.01}]
    fam, params = _fit("OpDecisionTreeClassifier", grid, X, y)
    one = fam.select_params(params, 0)
    fitted = FittedParams(family=fam.name, params=one, hyper=grid[0],
                          num_classes=2)
    imp = fam.feature_importances(fitted)
    assert imp is not None and imp.sum() > 0
    # features 0/1 carry the signal; sentinel slots must not drown them
    assert imp[0] + imp[1] > 0.5, imp


def test_default_grids_include_depth12():
    """Default tree grids match the reference's maxDepth {3, 6, 12}
    (DefaultSelectorParams.scala:37)."""
    for name in ("OpDecisionTreeClassifier", "OpRandomForestClassifier",
                 "OpGBTClassifier"):
        fam = MODEL_REGISTRY[name]
        depths = sorted({g["maxDepth"] for g in fam.default_grid("binary")})
        assert depths == [3, 6, 12], (name, depths)


# ---------------------------------------------------------------------------
# Sibling-subtraction chain grower == full-histogram chain grower
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,W,depth", [
    ("counts", 8, 5), ("counts", 16, 7), ("gh", 8, 6),
])
def test_chain_sibling_subtraction_parity(monkeypatch, mode, W, depth):
    """The Tb-gated sibling-subtraction path (fresh even-slot histograms +
    odd-slot reconstruction) must grow the same trees as the full
    per-level histogram path — CI only reaches the gate-off branch
    naturally (sweep batches on real TPU are the Tb >= 128 regime), so
    force both branches and compare all five outputs."""
    rng = np.random.RandomState(11)
    S, d, Tb, n_bins = 512, 6, 12, 16
    codes = jnp.asarray(rng.randint(0, n_bins, size=(S, d), dtype=np.int32))
    edges = jnp.asarray(
        np.sort(rng.randn(d, n_bins - 1).astype(np.float32), axis=1))
    k = 2 if mode == "counts" else 3
    # well-separated stats so split choices don't sit on numeric ties
    sw_list = [jnp.asarray(rng.rand(S, Tb).astype(np.float32) + 0.1)
               for _ in range(k)]
    fmasks = jnp.ones((Tb, d), bool)
    cfg = {"max_depth": jnp.full((Tb,), float(depth), jnp.float32),
           "min_instances": jnp.full((Tb,), 1.0, jnp.float32),
           "min_info_gain": jnp.full((Tb,), 1e-4, jnp.float32),
           "lam": jnp.full((Tb,), 1e-6, jnp.float32),
           "min_child_weight": jnp.zeros((Tb,), jnp.float32)}

    def grow():
        return T._grow_forest_capped(
            codes, edges, sw_list, fmasks, cfg,
            depth=depth, n_bins=n_bins, mode=mode, n_slots=W)

    monkeypatch.setattr(T, "_CHAIN_SIBLING_MIN_TB", 1 << 30)
    base = [np.asarray(a) for a in grow()]
    monkeypatch.setattr(T, "_CHAIN_SIBLING_MIN_TB", 1)
    sib = [np.asarray(a) for a in grow()]
    names = ("feat_lv", "thr_lv", "bin_lv", "base_lv", "node_s")
    for nm, a, b in zip(names, base, sib):
        np.testing.assert_array_equal(a, b, err_msg=nm)


# ---------------------------------------------------------------------------
# Per-tree compact columns: the growers contract and search only the columns
# each tree drew, and grow the same trees as over all d columns and a mask
# ---------------------------------------------------------------------------

def _subset_case(mode, k, Tb, seed=7, S=700, d=24, n_bins=16, p=0.3):
    """One table, strict per-tree subsets (one tree drew nothing), as both
    forms the growers take them in: (Tb, d) masks and the (Tb, d_sub)
    ascending index table padded with the sentinel d."""
    rng = np.random.RandomState(seed)
    codes = jnp.asarray(rng.randint(0, n_bins, (S, d)), jnp.int32)
    edges = jnp.asarray(np.sort(rng.randn(d, n_bins - 1), 1), jnp.float32)
    fm = rng.rand(Tb, d) < p
    fm[Tb // 2] = False
    d_sub = -(-int(fm.sum(1).max()) // 4) * 4
    assert d_sub < d
    cols = np.sort(np.where(fm, np.arange(d), d), 1)[:, :d_sub]
    # well-separated stats so split choices don't sit on numeric ties
    sw = [jnp.asarray(rng.rand(S, Tb).astype(np.float32) + 0.1)
          for _ in range(k)]
    cfg = {"max_depth": jnp.full((Tb,), 5.0, jnp.float32),
           "min_instances": jnp.full((Tb,), 1.0, jnp.float32),
           "min_info_gain": jnp.full((Tb,), 1e-4, jnp.float32),
           "lam": jnp.full((Tb,), 1e-6, jnp.float32),
           "min_child_weight": jnp.zeros((Tb,), jnp.float32)}
    return (codes, edges, sw, jnp.asarray(fm), cfg,
            jnp.asarray(cols, jnp.int32), n_bins, fm)


@pytest.mark.parametrize("sibling", [False, True])
@pytest.mark.parametrize("mode,k", [("counts", 2), ("counts", 5), ("gh", 3)])
def test_capped_grower_compact_columns_grow_the_same_trees(
        monkeypatch, mode, k, sibling):
    """Compact against full, slot-chain grower, sibling subtraction off (Tb
    below the gate) and on (above it): the same split column and bin at
    every level and slot, thresholds equal, the sample routed to the same
    leaves; no split uses a column its tree did not draw."""
    monkeypatch.setattr(T, "_CHAIN_SIBLING_MIN_TB", 8)
    Tb = 12 if sibling else 6
    codes, edges, sw, fmasks, cfg, cols, n_bins, fm = _subset_case(
        mode, k, Tb)

    def grow(**kw):
        return [np.asarray(a) for a in T._grow_forest_capped(
            codes, edges, sw, fmasks, cfg, depth=5, n_bins=n_bins,
            mode=mode, n_slots=8, **kw)]

    full, compact = grow(), grow(feat_idx=cols)
    for nm, a, b in zip(("feat_lv", "thr_lv", "bin_lv", "base_lv", "node_s"),
                        full, compact):
        np.testing.assert_array_equal(a, b, err_msg=nm)
    feat_lv, bin_lv = compact[0], compact[2]
    split = bin_lv < n_bins
    assert split.any()
    t_of = np.broadcast_to(np.arange(Tb)[:, None, None], feat_lv.shape)
    assert fm[t_of[split], feat_lv[split]].all()
    assert not split[Tb // 2].any()       # the tree that drew nothing


@pytest.mark.parametrize("B,n_trees,d,task", [
    (1, 50, 28, "classification"), (54, 16, 76, "classification"),
    (54, 16, 105, "classification"), (6, 5, 24, "regression"),
    (18, 16, 300, "regression"), (3, 7, 6, "classification"),
    (2, 3, 4, "regression")])
def test_feature_table_width_is_exact(B, n_trees, d, task):
    """d_sub is a bound, not an estimate: every tree's drawn set is its
    ``feat_idx`` row (ascending, sentinel-padded), the widest tree fills the
    row to within the rounding to 4, masks and index come from one table,
    and the masks are the draws the fit's own keys give."""
    p = T._rf_p_feat(d, task)
    fmasks, feat_idx = T._rf_feature_table(B, n_trees, d, p)
    assert fmasks.shape == (B, n_trees, d) and fmasks.dtype == bool
    seeds = jnp.asarray(T._rf_seeds(B))
    for b, t in ((0, 0), (B - 1, n_trees - 1)):
        want = jax.random.bernoulli(T._rf_tree_keys(seeds[b], t)[1], p, (d,))
        np.testing.assert_array_equal(fmasks[b, t], np.asarray(want))
    widest = int(fmasks.sum(-1).max())
    if feat_idx is None:
        assert max(4, -(-widest // 4) * 4) >= d
        return
    d_sub = feat_idx.shape[-1]
    assert feat_idx.shape == (B, n_trees, d_sub) and d_sub % 4 == 0
    assert widest <= d_sub < min(d, widest + 4)
    back = np.zeros_like(fmasks)
    b_i, t_i, c_i = np.nonzero(feat_idx < d)
    back[b_i, t_i, feat_idx[b_i, t_i, c_i]] = True
    np.testing.assert_array_equal(back, fmasks)      # nothing dropped
    assert (np.diff(feat_idx, axis=-1) >= 0).all()
    real = feat_idx[..., 1:] < d
    assert (np.diff(feat_idx, axis=-1)[real] > 0).all()
    assert T._rf_feature_table(B, n_trees, d, p)[1] is feat_idx   # cached


def _tree_batched_dots(jaxpr):
    """dot_generals with two batch dimensions (row block x tree): the
    compact path's contraction; the full-width one has the row block only."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += len(eqn.params["dimension_numbers"][1][0]) == 2
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _tree_batched_dots(sub)
    return n


@pytest.mark.parametrize("fam_name,d,compact", [
    ("OpRandomForestClassifier", 30, True),
    ("OpRandomForestRegressor", 30, True),
    ("OpRandomForestClassifier", 6, False),
    ("OpGBTClassifier", 30, False), ("OpXGBoostClassifier", 30, False),
    ("OpDecisionTreeClassifier", 30, False)])
def test_only_strict_subsets_trace_the_compact_path(fam_name, d, compact):
    """The width of the subset is the one thing the growers adapt to: a
    forest over a table wide enough for strict subsets traces the
    tree-batched contraction; one whose d_sub >= d, and the callers that
    pass no subset (boosted trees, XGBoost, single trees), trace none."""
    fam = MODEL_REGISTRY[fam_name]
    problem = "regression" if "Regressor" in fam_name else "binary"
    stock = fam.default_grid(problem)
    grid = [stock[0], stock[-1]]          # the shallowest and the deepest
    garr = {k: np.asarray(v) for k, v in fam.grid_to_arrays(grid).items()}
    n = 600
    jaxpr = jax.make_jaxpr(
        lambda X, y, W: fam.sweep_fit_batch(X, y, W, garr, 2))(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((len(grid), n), jnp.float32))
    assert (_tree_batched_dots(jaxpr.jaxpr) > 0) == compact
