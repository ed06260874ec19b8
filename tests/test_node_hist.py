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


@pytest.mark.parametrize("T,Wl,stride", [(5, 1, 1), (54, 7, 1), (54, 64, 1),
                                         (130, 16, 2), (20, 32, 2)])
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
