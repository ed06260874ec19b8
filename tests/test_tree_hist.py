"""Pallas tree-kernel tests: fused histogram and routing matmuls
(histeng/kernels.py). On the CPU test mesh the pallas path runs in interpret
mode (TG_TREE_PALLAS=1); the default CPU path is the XLA fallback — both are
checked against direct numpy computation."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from transmogrifai_tpu.histeng import hist_matmul


def _hist_direct(codes, A, nb):
    S, d = codes.shape
    B = A.shape[1]
    out = np.zeros((B, d * nb), np.float64)
    for f in range(d):
        for b in range(nb):
            m = (codes[:, f] == b).astype(np.float64)
            out[:, f * nb + b] = (A.astype(np.float64) * m[:, None]).sum(0)
    return out


def _descend_direct(codes, feat, bins, depth, nb):
    """Reference complete-heap descent: (n, T) leaf assignments."""
    n = codes.shape[0]
    T = feat.shape[0]
    node = np.zeros((n, T), np.int64)
    for lvl in range(depth):
        base = 2 ** lvl - 1
        for t in range(T):
            h = base + node[:, t]
            go = ((bins[t, h] < nb)
                  & (codes[np.arange(n), feat[t, h]] > bins[t, h]))
            node[:, t] = 2 * node[:, t] + go
    return node


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("shape", [(200, 5, 32, 3), (1100, 17, 16, 9)])
def test_hist_matmul(use_pallas, shape, monkeypatch):
    monkeypatch.setenv("TG_TREE_PALLAS", "1" if use_pallas else "0")
    S, d, nb, B = shape
    rng = np.random.RandomState(0)
    codes = rng.randint(0, nb, (S, d)).astype(np.int32)
    A = rng.randn(S, B).astype(np.float32)
    got = np.asarray(hist_matmul(jnp.asarray(codes), jnp.asarray(A), nb))
    want = _hist_direct(codes, A, nb)
    # bf16 accumulate tolerance
    assert np.allclose(got, want, rtol=2e-2, atol=2e-2 * np.abs(want).max())


@pytest.mark.parametrize("use_pallas", [False, True])
def test_hist_matmul_vmap_flattens(use_pallas, monkeypatch):
    monkeypatch.setenv("TG_TREE_PALLAS", "1" if use_pallas else "0")
    rng = np.random.RandomState(1)
    codes = rng.randint(0, 8, (300, 6)).astype(np.int32)
    Ab = rng.randn(4, 300, 5).astype(np.float32)
    got = np.asarray(jax.vmap(
        lambda a: hist_matmul(jnp.asarray(codes), a, 8))(
        jnp.asarray(Ab)))
    for v in range(4):
        want = _hist_direct(codes, Ab[v], 8)
        assert np.allclose(got[v], want, rtol=2e-2,
                           atol=2e-2 * np.abs(want).max())


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("shape", [(333, 11, 5, 4, 8, 3),
                                   (150, 7, 1, 3, 16, 1),
                                   (257, 9, 9, 6, 32, 4)])
def test_forest_leaf_sums(use_pallas, shape, monkeypatch):
    monkeypatch.setenv("TG_TREE_PALLAS", "1" if use_pallas else "0")
    jax.clear_caches()
    from transmogrifai_tpu.ops import forest
    n, d, T, depth, nb, k = shape
    H, L = 2 ** depth - 1, 2 ** depth
    rng = np.random.RandomState(2)
    codes = rng.randint(0, nb, (n, d)).astype(np.int32)
    feat = rng.randint(0, d, (T, H)).astype(np.int32)
    bins = rng.randint(0, nb, (T, H)).astype(np.int32)
    bins[rng.rand(T, H) < 0.3] = nb                   # stop sentinels
    aug = rng.randn(n, k).astype(np.float32)
    node = _descend_direct(codes, feat, bins, depth, nb)
    want = np.zeros((T, L, k))
    for t in range(T):
        np.add.at(want[t], node[:, t], aug.astype(np.float64))
    got = np.asarray(forest.forest_leaf_sums(
        jnp.asarray(codes), jnp.asarray(feat), jnp.asarray(bins),
        jnp.asarray(aug), depth=depth, n_bins=nb))
    assert np.allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_forest_predict(use_pallas, monkeypatch):
    monkeypatch.setenv("TG_TREE_PALLAS", "1" if use_pallas else "0")
    jax.clear_caches()
    from transmogrifai_tpu.ops import forest
    n, d, T, depth, nb, k = 270, 6, 4, 5, 32, 2
    H, L = 2 ** depth - 1, 2 ** depth
    rng = np.random.RandomState(3)
    codes = rng.randint(0, nb, (n, d)).astype(np.int32)
    feat = rng.randint(0, d, (T, H)).astype(np.int32)
    bins = rng.randint(0, nb + 1, (T, H)).astype(np.int32)
    leaf = rng.randn(T, L, k).astype(np.float32)
    node = _descend_direct(codes, feat, bins, depth, nb)
    want = np.zeros((n, k))
    for t in range(T):
        want += leaf[t, node[:, t]]
    got = np.asarray(forest.forest_predict(
        jnp.asarray(codes), jnp.asarray(feat), jnp.asarray(bins),
        jnp.asarray(leaf), depth=depth, n_bins=nb))
    assert np.allclose(got, want, rtol=1e-4, atol=1e-4)


def test_sentinel_codes_contribute_nothing():
    rng = np.random.RandomState(4)
    nb = 8
    codes = rng.randint(0, nb, (100, 3)).astype(np.int32)
    codes[50:, 1] = nb                       # sentinel rows/features
    A = rng.randn(100, 2).astype(np.float32)
    got = np.asarray(hist_matmul(jnp.asarray(codes), jnp.asarray(A), nb))
    # feature 1 histogram over sentinel rows is zero: total mass of feature 1
    # equals the A-sum over non-sentinel rows only
    f1 = got[:, 1 * nb:(1 + 1) * nb].sum(1)
    want = A[:50].sum(0)
    assert np.allclose(f1, want, rtol=2e-2, atol=1e-3)
