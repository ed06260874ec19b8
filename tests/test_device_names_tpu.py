"""The device names the benchmark's readers are pinned to, checked by
compiling for the chip without the chip (the TPU's compiler is installed
here and compiles for a described v5e): ``score_descent_s`` finds the forest
descent by the name its Pallas custom calls take from the jitted function
that encloses them, ``_predict_rf_chain_batch``. A ``jax.named_scope``
between that function and the ``pallas_call`` renames them (PR 24 found out
on the chip); one outside it, like the plan's ``stage.<ClassName>``, does
not. All chip-less compiles of the repo belong in this one file: only one
worker may load the TPU's library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip_branches(monkeypatch):
    """The kernels choose Pallas, compiled and not interpreted, from
    ``jax.default_backend()`` at trace time: steer them as on the chip."""
    import transmogrifai_tpu.histeng.kernels as kernels
    import transmogrifai_tpu.ops.forest as forest
    for mod in (kernels, forest):
        monkeypatch.setattr(mod, "_use_pallas", lambda: True)
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    yield
    jax.clear_caches()       # nothing traced this way may serve a CPU test


def test_the_descent_kernel_keeps_the_name_score_descent_s_reads(
        one_chip, chip_branches):
    from transmogrifai_tpu.models import trees
    B, T, depth, W, k, nb, n, d = 1, 50, 12, 256, 2, 32, 8192, 28

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    tables = [shape((B, T, depth, W), jnp.int32)] * 3
    args = (*tables, shape((B, T, W, k), jnp.float32),
            shape((B, T), jnp.float32), shape((d, nb - 1), jnp.float32),
            shape((n, d), jnp.float32))

    def chain(*a):       # as a plan segment's chain wraps each stage
        with jax.named_scope("stage.SelectedModel"):
            return trees._predict_rf_chain_batch(*a, n_bins=nb)
    text = jax.jit(chain).lower(*args).compile().as_text()
    kernels_named = set(re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))
    assert kernels_named, "no Pallas kernel in the compiled program"
    assert all("_predict_rf_chain_batch" in name for name in kernels_named), \
        kernels_named


def test_the_softmax_refit_compiles_for_the_chip_at_the_cells_size(one_chip):
    """``train-kddcup99``'s refit: one lane of 23 classes over the selector's
    900 000 rows (bucket 1 048 576) x 76 columns, float32 temporaries and
    HIGHEST-precision contractions. The chip's compiler takes it, and it
    asks for well under the chip's 16 GB."""
    from transmogrifai_tpu.models import linear
    n, d, C = 1048576, 76, 23

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda X, y, W, r, e: linear._fit_softmax_batch(X, y, W, r, e, C)
    ).lower(shape((n, d), jnp.float32), shape((n,), jnp.int32),
            shape((1, n), jnp.float32), shape((1,), jnp.float32),
            shape((1,), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
    assert "while" in compiled.as_text()       # the schedule stays a loop


@pytest.mark.parametrize("fit,lanes", [("linear", 18), ("glm", 24)])
def test_the_moment_based_sweep_fits_compile_for_the_chip_at_the_cells_size(
        one_chip, fit, lanes):
    """``train-nyctaxi``'s linear and generalised-linear sweeps: 18 and 24
    lanes over the selector's 3 600 000 rows (bucket 4 194 304) x 30
    columns. Before PR 30 every lane held its own standardised copy of the
    matrix (8 GB a temporary); from moments the chip's compiler is asked for
    a block's outer products and little else."""
    from transmogrifai_tpu.models import glm, linear
    n, d = 4194304, 30

    def shape(dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
    fn = linear._fit_linreg_batch if fit == "linear" else glm._fit_glm_batch
    grid = [shape((lanes,))] * (2 if fit == "linear" else 3)
    compiled = fn.lower(shape((n, d)), shape((n,)), shape((lanes, n)),
                        *grid).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert "while" in compiled.as_text()       # the row blocks stay a loop
