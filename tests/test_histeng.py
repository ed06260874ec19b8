"""Histogram engine (transmogrifai_tpu/histeng/): one tree-growth primitive
across the XLA/Pallas, mesh, and host backends — pinned K-blocked reduction
bit-exactness, host-backend bincount bit-equality with StreamingGBT's legacy
inline block, the ``hist.build`` chaos quarantine, and AOT zero-compile
cold start for tree sweep programs (docs/trees.md)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import transmogrifai_tpu.models.linear   # noqa: F401 (registers families)
import transmogrifai_tpu.models.trees    # noqa: F401
from transmogrifai_tpu import histeng
from transmogrifai_tpu.histeng import kernels as hk
from transmogrifai_tpu.impl.tuning import validators as _validators
from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
from transmogrifai_tpu.models.api import MODEL_REGISTRY
from transmogrifai_tpu.parallel import MeshSpec, make_mesh
from transmogrifai_tpu.robustness import faults

pytestmark = pytest.mark.hist

RF_GRID = [{"maxDepth": 2, "minInstancesPerNode": 5, "minInfoGain": 0.001,
            "numTrees": 3, "subsamplingRate": 1.0}]
LR_GRID = [{"regParam": r, "elasticNetParam": 0.0} for r in (0.01, 0.1)]


def _synth(n=333, d=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


# ---------------------------------------------------------------------------
# pinned K-blocked contraction: correctness + fixed combine order
# ---------------------------------------------------------------------------

def _hist_direct(codes, A, nb):
    S, d = codes.shape
    B = A.shape[1]
    out = np.zeros((B, d * nb), np.float64)
    for f in range(d):
        for b in range(nb):
            m = (codes[:, f] == b).astype(np.float64)
            out[:, f * nb + b] = (A.astype(np.float64) * m[:, None]).sum(0)
    return out


@pytest.mark.parametrize("S", [200, 333, 1029])
def test_pinned_contraction_matches_direct_reference(S, monkeypatch):
    monkeypatch.setenv("TG_TREE_PALLAS", "0")
    nb, d, B = 16, 5, 3
    rng = np.random.RandomState(0)
    codes = rng.randint(0, nb, (S, d)).astype(np.int32)
    A = rng.randn(S, B).astype(np.float32)
    got = np.asarray(histeng.build_hist(jnp.asarray(codes),
                                        jnp.asarray(A), nb))
    want = _hist_direct(codes, A, nb)
    assert np.allclose(got, want, rtol=2e-2, atol=2e-2 * np.abs(want).max())


def test_exact_mode_integer_stats_are_exact(monkeypatch):
    """exact=True keeps f32 HIGHEST end to end; integer-valued stats sum
    without rounding even through the K-blocked combine."""
    monkeypatch.setenv("TG_TREE_PALLAS", "0")
    nb, S, d = 8, 500, 4
    rng = np.random.RandomState(1)
    codes = rng.randint(0, nb, (S, d)).astype(np.int32)
    A = rng.randint(0, 7, (S, 2)).astype(np.float32)
    got = np.asarray(histeng.build_hist(jnp.asarray(codes),
                                        jnp.asarray(A), nb, exact=True))
    np.testing.assert_array_equal(got, _hist_direct(codes, A, nb))


def test_tree_combine_is_fixed_order():
    """The combine is the pinned expression ((p0+p1)+(p2+p3))+p4 — bit for
    bit, including the odd-leftover path."""
    rng = np.random.RandomState(2)
    p = jnp.asarray(rng.randn(5, 3, 2).astype(np.float32))
    got = np.asarray(hk._tree_combine(p))
    want = np.asarray(((p[0] + p[1]) + (p[2] + p[3])) + p[4])
    np.testing.assert_array_equal(got, want)


def _combine_strided(parts):
    """`_tree_combine` as it stood until PR 31 (strided slices)."""
    while parts.shape[0] > 1:
        h = parts.shape[0] // 2
        s = parts[0:2 * h:2] + parts[1:2 * h:2]
        if parts.shape[0] % 2:
            s = jnp.concatenate([s, parts[2 * h:]], axis=0)
        parts = s
    return parts[0]


#: the pinned association written out: neighbours added, an odd leftover
#: carried to the next round
_PINNED = {
    1: lambda p: p[0],
    2: lambda p: p[0] + p[1],
    3: lambda p: (p[0] + p[1]) + p[2],
    5: lambda p: ((p[0] + p[1]) + (p[2] + p[3])) + p[4],
    7: lambda p: ((p[0] + p[1]) + (p[2] + p[3])) + ((p[4] + p[5]) + p[6]),
    8: lambda p: (((p[0] + p[1]) + (p[2] + p[3]))
                  + ((p[4] + p[5]) + (p[6] + p[7]))),
}


@pytest.mark.parametrize("under_mesh", [False, True],
                         ids=["one_device", "engine_mesh"])
@pytest.mark.parametrize("tail", [(6,), (3, 5), (2, 3, 4)],
                         ids=["2d", "3d", "4d"])
@pytest.mark.parametrize("K", sorted(_PINNED))
def test_tree_combine_spellings_give_the_pinned_bits(K, tail, under_mesh):
    """Both spellings of the combine (static slices on one device, the
    reshape-halving one traced under an engine mesh) equal the written-out
    pinned expression AND the strided-slice form they replaced, bit for bit
    (uint32 view), for every K, power of two or not."""
    rng = np.random.RandomState(100 * K + len(tail))
    # magnitudes spread over six decades: another association would show
    p = jnp.asarray((rng.randn(K, *tail)
                     * 10.0 ** rng.randint(-3, 4, (K, *tail))
                     ).astype(np.float32))
    want = np.asarray(_PINNED[K](p)).view(np.uint32)
    old = np.asarray(jax.jit(_combine_strided)(p)).view(np.uint32)
    np.testing.assert_array_equal(old, want)
    fn = jax.jit(hk._tree_combine)

    def traced():
        return {e.primitive.name
                for e in jax.make_jaxpr(hk._tree_combine)(p).jaxpr.eqns}
    if under_mesh:
        with histeng.engine_mesh(make_mesh(MeshSpec(data=4, model=2))):
            assert hk._combine_form() == "halving"
            prims = traced()
            got = np.asarray(fn(p))
        # the halving spelling reshapes the array; the fused one only
        # takes static slices of it
        assert ("reshape" in prims) == (K > 1)
    else:
        assert hk._combine_form() == "fused"
        assert "reshape" not in traced()
        got = np.asarray(fn(p))
    assert got.shape == tail
    np.testing.assert_array_equal(got.view(np.uint32), want)


@pytest.mark.parametrize("family", [
    "OpDecisionTreeClassifier", "OpRandomForestClassifier",
    "OpGBTClassifier", "OpXGBoostClassifier", "OpRandomForestRegressor",
    "OpGBTRegressor"])
def test_tree_families_put_the_combine_on_their_spans(family, monkeypatch):
    """``histShards`` (K) and ``combine`` (the spelling `_tree_combine`
    traces, by its own test of the engine mesh) are what every tree family
    adds to ``sweep.family`` / ``selector.refit``; the forest keeps its
    chunk count and column width on top, a boosted family the trees a round
    grows side by side and the lanes they are laid on (PR 43: a grid of one
    point is one tree on one lane); other families add neither."""
    fam = MODEL_REGISTRY[family]
    grid = [{"maxDepth": 3}]
    own = fam.fit_span_attrs(4000, 12, grid, 2, True)
    assert (own["histShards"], own["combine"]) == (8, "fused")
    # the growers' sample: every row of a table under the sweep's cap
    assert own["sampleRows"] == 4000
    assert fam.fit_span_attrs(10 ** 7, 12, grid, 2, True)["sampleRows"] == 8192
    assert (set(own) - {"histShards", "combine", "sampleRows"}
            == ({"configChunks", "featSubset"} if "Forest" in family
                else {"treeLanes", "treeLanesPadded"} if "GB" in family
                else set()))
    if "treeLanes" in own:
        assert (own["treeLanes"], own["treeLanesPadded"]) == (1, 1)
    with histeng.engine_mesh(make_mesh(MeshSpec(data=4, model=2))):
        assert fam.fit_span_attrs(4000, 12, grid, 2, True)[
            "combine"] == "halving"
    monkeypatch.setenv("TG_HIST_SHARDS", "5")
    assert fam.fit_span_attrs(4000, 12, grid, 2, False)["histShards"] == 5
    assert "combine" not in MODEL_REGISTRY[
        "OpLogisticRegression"].fit_span_attrs(4000, 12, LR_GRID, 2, True)


def test_pinned_kernel_bit_exact_under_mesh_sharding(monkeypatch):
    """The determinism contract at kernel level: tracing the contraction
    under an engine mesh context (row blocks constrained to 'data') yields
    the same BITS as the plain single-device call — the per-block GEMMs are
    shape-identical local work and the combine order is pinned."""
    monkeypatch.setenv("TG_TREE_PALLAS", "0")
    nb, S, d, B = 32, 333, 6, 4
    rng = np.random.RandomState(3)
    codes = jnp.asarray(rng.randint(0, nb, (S, d)).astype(np.int32))
    A = jnp.asarray(rng.randn(S, B).astype(np.float32))
    plain = np.asarray(histeng.build_hist(codes, A, nb))
    mesh = make_mesh(MeshSpec(data=4, model=2))
    fn = jax.jit(lambda c, a: histeng.build_hist(c, a, nb))
    with histeng.engine_mesh(mesh):
        sharded = np.asarray(fn(codes, A))
    assert histeng.current_engine_mesh() is None
    np.testing.assert_array_equal(sharded, plain)


@pytest.mark.parametrize("per_tree", [False, True])
def test_build_node_hist_under_jit_with_engine_mesh(monkeypatch, per_tree):
    """The node-resolved entry point, jitted and traced under
    `engine_mesh`, shards its row blocks and gives the single-device bits:
    with codes shared by the trees, and with per-tree codes (the forest's
    compact columns: the batched contraction keeps the pinned form).
    `engine_mesh` enters ``jax.set_mesh``, so it wraps the jitted call from
    outside — entering it inside a trace is refused by jax."""
    monkeypatch.setenv("TG_TREE_PALLAS", "0")
    rng = np.random.RandomState(5)
    S, d, nb, T, Wl = 257, 5, 8, 3, 4
    codes = jnp.asarray(rng.randint(
        0, nb, (S, T, d) if per_tree else (S, d)).astype(np.int32))
    node = jnp.asarray(rng.randint(0, Wl, (S, T)).astype(np.int32))
    sw = jnp.asarray(rng.randn(S, T).astype(np.float32))
    plain = np.asarray(histeng.build_node_hist(codes, node, [sw], nb,
                                               n_nodes=Wl))
    mesh = make_mesh(MeshSpec(data=4, model=2))
    fn = jax.jit(lambda c, n, s: histeng.build_node_hist(c, n, [s], nb,
                                                         n_nodes=Wl))
    with histeng.engine_mesh(mesh):
        sharded = np.asarray(fn(codes, node, sw))
    assert histeng.current_engine_mesh() is None
    assert jax.sharding.get_abstract_mesh().empty
    np.testing.assert_array_equal(sharded, plain)

    def inside(c, n, s):
        with histeng.engine_mesh(mesh):
            return histeng.build_node_hist(c, n, [s], nb, n_nodes=Wl)
    with pytest.raises(ValueError, match="set_mesh"):
        jax.jit(inside)(codes, node, sw)
    assert histeng.current_engine_mesh() is None


def test_build_node_hist_device_layout_matches_flat_kernel():
    """The structured (k, n_nodes, T, d, nb) output is a pure reshape of
    the flat kernel's lane layout."""
    rng = np.random.RandomState(4)
    S, d, nb, T, Wl, k = 256, 5, 8, 6, 4, 2
    codes = jnp.asarray(rng.randint(0, nb, (S, d)).astype(np.int32))
    node = jnp.asarray(rng.randint(0, Wl, (S, T)).astype(np.int32))
    sws = [jnp.asarray(rng.randn(S, T).astype(np.float32))
           for _ in range(k)]
    got = np.asarray(histeng.build_node_hist(codes, node, sws, nb,
                                             n_nodes=Wl))
    flat = np.asarray(histeng.node_hist_matmul(codes, node, sws, Wl, nb))
    np.testing.assert_array_equal(
        got, flat.reshape(k, Wl, T, d, nb))


# ---------------------------------------------------------------------------
# host backend: bit-equality with the legacy StreamingGBT inline block
# ---------------------------------------------------------------------------

def _legacy_level_stats(X, edges, node, r, n_nodes, d, nb):
    """Frozen copy of the flat-bincount block that used to live inline in
    streaming/model.py extract_level — the regression reference."""
    n = X.shape[0]
    Xt = np.ascontiguousarray(X.T, dtype=np.float64)
    flat = np.empty((d, n), dtype=np.int64)
    base = node * (d * nb)
    for j in range(d):
        code = np.searchsorted(edges[j], Xt[j], side="left")
        np.add(base, j * nb + code, out=flat[j])
    size = n_nodes * d * nb
    fl = flat.ravel()
    shape = (n_nodes, d, nb)
    return {
        "cnt": np.bincount(fl, minlength=size)
        .astype(np.float64).reshape(shape),
        "sum": np.bincount(fl, weights=np.tile(r, d),
                           minlength=size).reshape(shape),
        "sumsq": np.bincount(fl, weights=np.tile(r * r, d),
                             minlength=size).reshape(shape),
    }


def test_host_backend_bit_equal_legacy_block():
    rng = np.random.RandomState(5)
    n, d, nb, n_nodes = 777, 6, 8, 4
    X = rng.randn(n, d).astype(np.float32)
    edges = np.sort(rng.randn(d, nb - 1), axis=1)
    edges[:, -2:] = np.inf                       # unused slots, like SPDT
    node = rng.randint(0, n_nodes, n).astype(np.int64)
    r = rng.randn(n)
    want = _legacy_level_stats(X, edges, node, r, n_nodes, d, nb)
    codes = histeng.bin_codes_host(X, edges)
    cnt, s, sq = histeng.build_node_hist(codes, node, [None, r, r * r],
                                         nb, n_nodes=n_nodes)
    # BIT equality: identical flat-index traversal order, identical f64
    # accumulation sequence
    assert cnt.tobytes() == want["cnt"].tobytes()
    assert s.tobytes() == want["sum"].tobytes()
    assert sq.tobytes() == want["sumsq"].tobytes()


@pytest.mark.stream
def test_streaming_fit_bit_equal_legacy_engine(monkeypatch):
    """StreamingGBT routed through the engine's host backend grows
    bit-identical trees to the legacy inline-bincount implementation
    (same f0, same thresholds, same leaves — byte compare)."""
    from types import SimpleNamespace

    from transmogrifai_tpu.streaming import model as smod
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import OPVector, RealNN

    rng = np.random.RandomState(6)
    n, d = 400, 5
    X = rng.randn(n, d).astype(np.float32)
    y = (X @ rng.randn(d) > 0).astype(np.float32)
    tbl = FeatureTable({"label": Column(RealNN, y, None),
                        "vec": Column(OPVector, X, None)}, n)

    def fit_once():
        est = smod.StreamingGBT(problem="binary", num_trees=2, max_depth=3,
                                n_bins=8)
        est.input_features = (SimpleNamespace(name="label"),
                              SimpleNamespace(name="vec"))
        return est.fit(tbl)

    engine_model = fit_once()

    def legacy_build(codes, node, stats, nb, *, n_nodes=1, **kw):
        # reconstruct the legacy block from the engine call's inputs: the
        # engine's (d, n) codes ARE the legacy searchsorted output, so
        # only the bincount arithmetic is under test here
        d_, n_ = codes.shape
        flat = np.empty((d_, n_), dtype=np.int64)
        base = node * (d_ * nb)
        for j in range(d_):
            np.add(base, j * nb + codes[j], out=flat[j])
        size = n_nodes * d_ * nb
        fl = flat.ravel()
        out = np.empty((len(stats), n_nodes, d_, nb), np.float64)
        for i, w in enumerate(stats):
            if w is None:
                out[i] = (np.bincount(fl, minlength=size)
                          .astype(np.float64).reshape(n_nodes, d_, nb))
            else:
                out[i] = np.bincount(fl, weights=np.tile(w, d_),
                                     minlength=size
                                     ).reshape(n_nodes, d_, nb)
        return out

    monkeypatch.setattr(smod, "build_node_hist", legacy_build)
    legacy_model = fit_once()

    assert engine_model.f0 == legacy_model.f0
    assert len(engine_model.trees) == len(legacy_model.trees)
    for te, tl in zip(engine_model.trees, legacy_model.trees):
        for fe, fl_ in zip(te["feat_lv"], tl["feat_lv"]):
            np.testing.assert_array_equal(fe, fl_)
        for he, hl in zip(te["thr_lv"], tl["thr_lv"]):
            assert he.tobytes() == hl.tobytes()
        assert te["leaf"].tobytes() == tl["leaf"].tobytes()


# ---------------------------------------------------------------------------
# chaos: hist.build -> family quarantine
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_hist_build_chaos_quarantines_tree_family():
    """An armed ``hist.build`` raise quarantines the tree family before
    its histogram programs build — typed reason, NaN placeholder — and the
    linear families still race (bit_equal=False is the documented promise:
    the winner may legitimately differ from a fault-free run)."""
    X, y = _synth(n=300)
    models = [(MODEL_REGISTRY["OpLogisticRegression"], LR_GRID),
              (MODEL_REGISTRY["OpRandomForestClassifier"], RF_GRID)]
    with faults.injected({"hist.build": {"mode": "raise", "nth": 1}}):
        best = OpCrossValidation(num_folds=2, seed=0).validate(
            models, X, y, "binary", "AuROC", True, 2)
        assert faults.fired_counts() == {"hist.build": {"raise": 1}}
    q = {q["family"]: q for q in best.quarantined}
    assert set(q) == {"OpRandomForestClassifier"}
    assert "TransientFaultError" in q["OpRandomForestClassifier"]["reason"]
    assert "hist.build" in q["OpRandomForestClassifier"]["reason"]
    assert best.family_name == "OpLogisticRegression"
    rf = next(r for r in best.results
              if r.family == "OpRandomForestClassifier")
    assert np.all(np.isnan(rf.fold_metrics))


def test_hist_build_gate_is_keyed_per_family():
    """The gate passes the family name as the fault key, so a schedule can
    target one family; linear families never call the gate."""
    X, y = _synth(n=300)
    models = [(MODEL_REGISTRY["OpLogisticRegression"], LR_GRID),
              (MODEL_REGISTRY["OpRandomForestClassifier"], RF_GRID)]
    with faults.injected({"hist.build": {
            "mode": "raise", "nth": 1,
            "key": "OpLogisticRegression"}}):
        best = OpCrossValidation(num_folds=2, seed=0).validate(
            models, X, y, "binary", "AuROC", True, 2)
        # keyed to a family that never builds histograms: nothing fires
        assert faults.fired_counts() == {}
    assert not best.quarantined


# ---------------------------------------------------------------------------
# AOT: tree sweep programs (single-device AND mesh) zero-compile re-train
# ---------------------------------------------------------------------------

@pytest.mark.aot
def test_tree_sweep_aot_zero_compile_single_and_mesh(tmp_path, monkeypatch):
    """Mirrors the PR 15 cross-process sweep test for tree families: the
    first sweeps populate TG_AOT_STORE (one single-device program, one
    mesh program — mesh fingerprints pin axis sizes), the second pass
    (fused cache + ledger cleared, sessions closed: a fresh process in
    miniature) deserializes both — zero sweep-subsystem ledger builds and
    bit-equal fold metrics."""
    from transmogrifai_tpu.observability import ledger as lg
    from transmogrifai_tpu.programstore import store as ps

    monkeypatch.setenv("TG_AOT_STORE", str(tmp_path / "treestore"))
    monkeypatch.setenv("TG_MESH_FORCE", "1")
    X, y = _synth(n=333)
    models = [(MODEL_REGISTRY["OpRandomForestClassifier"], RF_GRID)]
    mesh = make_mesh(MeshSpec(data=4, model=2))

    _validators._FUSED_CACHE.clear()
    first = OpCrossValidation(num_folds=2, seed=0).validate(
        models, X, y, "binary", "AuROC", True, 2)
    first_m = OpCrossValidation(num_folds=2, seed=0, mesh=mesh).validate(
        models, X, y, "binary", "AuROC", True, 2)
    assert ps.stats()["exports"] >= 2
    assert ps.stats()["exportErrors"] == 0

    _validators._FUSED_CACHE.clear()
    lg.ledger().clear()
    ps.close_sessions()
    mark = lg.ledger().mark()
    second = OpCrossValidation(num_folds=2, seed=0).validate(
        models, X, y, "binary", "AuROC", True, 2)
    second_m = OpCrossValidation(num_folds=2, seed=0, mesh=mesh).validate(
        models, X, y, "binary", "AuROC", True, 2)
    sweep_builds = [r for r in lg.ledger().since(mark)
                    if r.subsystem == "sweep"]
    assert sweep_builds == [], [r.to_json() for r in sweep_builds]
    assert ps.stats()["hits"].get("sweep", 0) >= 2
    for a, b in ((first, second), (first_m, second_m)):
        np.testing.assert_array_equal(a.results[0].fold_metrics,
                                      b.results[0].fold_metrics)
    # and the engine keeps mesh == single-device bytes through the AOT path
    np.testing.assert_array_equal(second.results[0].fold_metrics,
                                  second_m.results[0].fold_metrics)


# ---------------------------------------------------------------------------
# no-leak fixture probe
# ---------------------------------------------------------------------------

def test_no_hist_engine_leak_fixture_probe():
    """Companion to the conftest ``_no_hist_engine_leak`` fixture: entry
    here must see a clean engine (no ambient mesh context), and the oracle
    agrees."""
    from transmogrifai_tpu.robustness import oracles
    assert histeng.current_engine_mesh() is None
    assert oracles.histeng_violations() == []
