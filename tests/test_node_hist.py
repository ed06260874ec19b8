"""node_hist_matmul parity: the production XLA contraction must equal the
explicit masked-A_cat reference."""
import numpy as np
import pytest


def _case(T, Wl, stride, seed=0):
    rng = np.random.RandomState(seed)
    S, d, nb, k = 512, 9, 8, 3
    codes = rng.randint(0, nb, size=(S, d)).astype(np.int32)
    node = (rng.randint(0, max(stride * Wl, 1), size=(S, T))
            .astype(np.int32))
    sw = [rng.randn(S, T).astype(np.float32) for _ in range(k)]
    return S, d, nb, k, codes, node, sw


def _reference(codes, node, sw, Wl, nb, stride, k):
    import jax.numpy as jnp
    from transmogrifai_tpu.histeng import hist_matmul
    S = codes.shape[0]
    T = node.shape[1]
    j = stride * np.arange(Wl, dtype=np.int32)[None, :, None]
    n_oh = (node[:, None, :] == j).astype(np.float32)
    A = np.concatenate([n_oh * s[:, None, :] for s in sw],
                       axis=1).reshape(S, k * Wl * T)
    return np.asarray(hist_matmul(jnp.asarray(codes), jnp.asarray(A), nb))


# the narrow ones (PR 43): under 32 trees the tree lanes follow the count
NARROW = [(1, 1, 1), (1, 4, 1), (1, 64, 1), (1, 256, 1), (1, 128, 2),
          (2, 32, 1), (3, 7, 1), (8, 16, 2), (16, 8, 1)]


@pytest.mark.parametrize("T,Wl,stride", [(5, 1, 1), (54, 7, 1), (54, 64, 1),
                                         (130, 16, 2), (20, 32, 2)] + NARROW)
def test_node_hist_matches_acat(T, Wl, stride):
    import jax.numpy as jnp
    from transmogrifai_tpu.histeng import node_hist_matmul
    S, d, nb, k, codes, node, sw = _case(T, Wl, stride)
    out = np.asarray(node_hist_matmul(
        jnp.asarray(codes), jnp.asarray(node),
        [jnp.asarray(s) for s in sw], Wl, nb, stride=stride))
    ref = _reference(codes, node, sw, Wl, nb, stride, k)
    assert out.shape == ref.shape == (k * Wl * T, d * nb)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("T,Wl,stride", [(1, 1, 1), (1, 256, 1), (1, 128, 2),
                                         (2, 32, 1), (5, 7, 1), (16, 8, 2)])
def test_narrow_trees_equal_the_32_lane_form(T, Wl, stride):
    """Up to 32 trees the tree lanes follow the tree count (PR 43); before,
    they were laid on 32. With integer-valued stats every product and every
    float32 sum is exact, so the narrow form equals the 32-lane form, built
    here as `node_hist_matmul` built it, to the bit."""
    import jax.numpy as jnp
    from transmogrifai_tpu.histeng import node_hist_matmul
    from transmogrifai_tpu.histeng.kernels import _node_hist_xla
    rng = np.random.RandomState(100 * T + Wl)
    S, d, nb, k = 512, 9, 8, 3
    codes = jnp.asarray(rng.randint(0, nb, size=(S, d)).astype(np.int32))
    node = rng.randint(0, stride * Wl, size=(S, T)).astype(np.int32)
    sw = [rng.randint(-3, 4, (S, T)).astype(np.float32) for _ in range(k)]
    got = np.asarray(node_hist_matmul(
        codes, jnp.asarray(node), [jnp.asarray(s) for s in sw], Wl, nb,
        stride=stride))
    Wl_eff = -(-max(Wl, 4) // 4) * 4            # 128 lanes / 32 trees
    wide = np.asarray(_node_hist_xla(
        codes,
        jnp.asarray(np.pad(node, ((0, 0), (0, 32 - T)), constant_values=-1)),
        jnp.asarray(np.stack([np.pad(s, ((0, 0), (0, 32 - T))) for s in sw])),
        Wl_eff, nb, stride, k))
    want = (wide.reshape(k, Wl_eff, 32, d * nb)[:, :Wl, :T]
            .reshape(k * Wl * T, d * nb))
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("T,Wl,columns", [
    (1, 256, 3 * 256 * 1), (1, 1, 3 * 128 * 1), (5, 7, 3 * 16 * 8),
    # the sweeps' shapes, pinned as they were: 54 trees on 64 lanes, and a
    # multiple of 128 above
    (54, 64, 3 * 64 * 64), (130, 16, 3 * 16 * 256)])
def test_stat_columns_follow_the_tree_count(T, Wl, columns):
    """The level contraction is ``k x Wl_eff x T_pad`` stat columns wide
    (`tree_lane_shape`): read off the lowered program."""
    import re
    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.histeng import node_hist_matmul
    from transmogrifai_tpu.histeng.kernels import tree_lane_shape
    S, d, nb, k, codes, node, sw = _case(T, Wl, 1)
    text = jax.jit(lambda c, n, s: node_hist_matmul(c, n, s, Wl, nb)).lower(
        jnp.asarray(codes), jnp.asarray(node),
        [jnp.asarray(s) for s in sw]).as_text()
    wide = re.findall(r"dot_general[^\n]*: \(tensor<8x64x(\d+)xbf16>, "
                      rf"tensor<8x64x{d * nb}xbf16>\)", text)
    assert wide == [str(columns)]
    T_pad, Wl_eff = tree_lane_shape(T, Wl)
    assert k * Wl_eff * T_pad == columns
    assert T_pad >= T and Wl_eff >= Wl and Wl_eff * T_pad % 128 == 0


@pytest.mark.parametrize("T,Wl,stride,S", [(5, 1, 1, 512), (54, 7, 1, 512),
                                           (20, 32, 2, 512), (7, 4, 1, 333),
                                           (3, 2, 2, 5)])
def test_node_hist_per_tree_columns_match_shared(T, Wl, stride, S):
    """Per-tree codes (S, T, d_sub): every tree's block of the batched
    contraction equals the shared-codes histogram read at that tree's own
    columns, and a sentinel column (code n_bins) is all zeros. Integer-ish
    stats keep the bf16 sums exact, so the two contractions agree to the
    bit whatever order the rows are summed in (S=333 pads a row block,
    S=5 < K runs unblocked)."""
    import jax.numpy as jnp
    from transmogrifai_tpu.histeng import build_node_hist
    rng = np.random.RandomState(T)
    d, nb, k, d_sub = 9, 8, 3, 4
    codes = rng.randint(0, nb, size=(S, d)).astype(np.int32)
    node = rng.randint(0, max(stride * Wl, 1), size=(S, T)).astype(np.int32)
    sw = [jnp.asarray(rng.randint(-3, 4, (S, T)).astype(np.float32))
          for _ in range(k)]
    cols = np.stack([np.sort(rng.choice(d, d_sub, replace=False))
                     for _ in range(T)])
    cols[0, -1] = d                                   # one short tree
    per_tree = np.where(cols[None] < d,
                        codes[:, np.minimum(cols, d - 1)], nb)   # (S, T, 4)
    full = np.asarray(build_node_hist(
        jnp.asarray(codes), jnp.asarray(node), sw, nb, n_nodes=Wl,
        stride=stride))                               # (k, Wl, T, d, nb)
    got = np.asarray(build_node_hist(
        jnp.asarray(per_tree), jnp.asarray(node), sw, nb, n_nodes=Wl,
        stride=stride))
    assert got.shape == (k, Wl, T, d_sub, nb)
    for t in range(T):
        for c in range(d_sub):
            want = (full[:, :, t, cols[t, c]] if cols[t, c] < d
                    else np.zeros((k, Wl, nb), np.float32))
            np.testing.assert_array_equal(got[:, :, t, c], want)
