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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


def test_the_boosted_chain_predict_compiles_at_the_cells_size(
        one_chip, chip_branches):
    """``train-higgs``'s winner over the whole table: 20 chain trees of
    depth 12 with 256 slots over 8 388 608 rows x 28 reals in ONE kernel
    call, named after ``_predict_gbt_chain_batch`` (``hg_eval_descent_s``
    and ``hg_closing_descent_s`` find it so). The chip's compiler takes the
    block body, which builds its select columns a chunk, and the program's
    temporaries are the kernel's bfloat16 code block (n x 128 x 2) and its
    float32 output (n x 128 x 4): 6.4 GB where int32 codes made 8.6."""
    from transmogrifai_tpu.models import trees
    B, T, C, depth, W, nb, n, d = 1, 20, 1, 12, 256, 32, 8388608, 28

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    tables = [shape((B, T, C, depth, W), jnp.int32)] * 3
    args = (*tables, shape((B, T, C, W), jnp.float32),
            shape((B, C), jnp.float32), shape((B,), jnp.float32),
            shape((B, T), jnp.float32), shape((d, nb - 1), jnp.float32),
            shape((n, d), jnp.float32))
    compiled = trees._predict_gbt_chain_batch.lower(
        *args, n_bins=nb).compile()
    kernels_named = re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())
    assert len(kernels_named) == 1, kernels_named
    assert "_predict_gbt_chain_batch" in kernels_named[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 6.6e9


def test_the_chain_predict_compiles_for_a_wide_table(one_chip, chip_branches):
    """A tree winner on ``train-tweets``'s 546 derived columns: the block
    builds select columns of five 128-row tiles, 548 of their rows compared
    and the rest zeros, and the chip's compiler takes that too."""
    from transmogrifai_tpu.ops import forest
    T, depth, W, k, nb, n, d = 20, 12, 256, 1, 32, 8192, 546

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    tables = [shape((T, depth, W), jnp.int32)] * 3
    compiled = jax.jit(forest.forest_predict_chain,
                       static_argnames="n_bins").lower(
        shape((n, d), jnp.int32), *tables, shape((T, W, k), jnp.float32),
        n_bins=nb).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("trees", [1, 2, 5])
def test_a_few_trees_leaf_sums_compile_for_the_chip(one_chip, chip_branches,
                                                    trees):
    """A boosted winner's refit sums the leaves of ONE tree a round
    (`train-higgs`: 65 536 sampled rows, 256 slots, G and H). The block of
    trees follows the tree count (PR 43) but stops at `_DIAG_MIN_BLOCK`: the
    chip's compiler takes the histogram kernel with a code block of four
    columns and refuses one of one or two at 64 leaves and more (the lane
    repeat asks 29-58 MB of VMEM for 16), which interpret mode does not
    show."""
    from transmogrifai_tpu.models import trees as tr
    S, J, L = 65536, 2, 256
    node = jax.ShapeDtypeStruct((S, trees), jnp.int32, sharding=one_chip)
    cols = jax.ShapeDtypeStruct((S, J, trees), jnp.float32,
                                sharding=one_chip)
    text = jax.jit(lambda n, a: tr._diag_leaf_hist(n, a, L)).lower(
        node, cols).compile().as_text()
    (kernel,) = re.findall(r"= (\S+) custom-call\([^\n]*tpu_custom_call", text)
    block = max(tr._DIAG_MIN_BLOCK, 1 << (trees - 1).bit_length())
    assert kernel.startswith(f"f32[{J * block},{block * L}]")


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


# The histogram engine's pinned contraction at one production shape each:
# a boosted-trees level of ``train-airline`` (8 192 sampled rows, 105
# columns x 32 bins, k.Wl.T_pad = 3 072 stat columns) through
# `_hist_xla_pinned`, and a forest chunk's level of the same cell (48 trees
# with 24 drawn columns each, 2 class planes x 16 slots) through
# `_node_hist_xla_per_tree`.
_S, _NB = 8192, 32


def _pinned_hist(shape):
    from transmogrifai_tpu.histeng import kernels
    d, B = 105, 3072
    args = (shape((_S, d), jnp.int32), shape((_S, B), jnp.float32))
    sizes = {"partials": 8 * B * d * _NB * 4, "one_hot": _S * d * _NB * 2,
             "stats": _S * B * 2}
    return (lambda c, a: kernels._hist_xla_pinned(c, a, _NB)), args, sizes


def _per_tree_hist(shape):
    from transmogrifai_tpu.histeng import kernels
    T, dw, k, Wl = 48, 24, 2, 16
    args = (shape((_S, T, dw), jnp.int32), shape((_S, T), jnp.int32),
            *[shape((_S, T), jnp.float32)] * k)
    # the tree-batched contraction wants the one-hot in another layout:
    # the compiler keeps three buffers of its size (strided form the same)
    sizes = {"partials": 8 * T * k * Wl * dw * _NB * 4,
             "one_hot": 3 * _S * T * dw * _NB * 2,
             "stats": _S * T * k * Wl * 2}
    return (lambda c, n, *sw: kernels._node_hist_xla_per_tree(
        c, n, list(sw), Wl, _NB, 1)), args, sizes


@pytest.mark.parametrize("piece", [_pinned_hist, _per_tree_hist])
def test_the_pinned_combine_is_one_fused_pass_on_the_chip(one_chip, piece):
    """On one device `_tree_combine` takes static slices of the K partials
    and the chip's compiler makes one elementwise pass of it: no ``gather``
    and no ``dynamic-update-slice`` (the strided slices it replaced in PR 31
    lowered to four and to 212 update loops at the first shape), and no
    temporary beyond the partials and the contraction's two bfloat16
    operands, the bin one-hot and the masked statistics."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    fn, args, sizes = piece(shape)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    assert " dynamic-update-slice(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= sum(
        sizes.values())


def _all_reduce_group_sizes(text):
    """The replica-group size of every ``all-reduce`` of an optimized
    module, in either spelling: ``{{0,1},{2,3}}`` or ``[2,2]<=[4]``."""
    sizes = []
    for line in text.splitlines():
        if not re.search(r" all-reduce(-start)?\(", line):
            continue
        listed = re.search(r"replica_groups=\{(\{[\d,]+\}(?:,\{[\d,]+\})*)\}",
                           line)
        iota = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
        if listed:
            sizes += [g.count(",") + 1
                      for g in re.findall(r"\{([\d,]+)\}", listed.group(1))]
        elif iota:
            sizes.append(int(iota.group(2)))
        else:       # empty groups: every device of the program
            sizes.append(-1)
    return sizes


@pytest.mark.parametrize("piece", [_pinned_hist, _per_tree_hist])
def test_the_mesh_combine_sums_two_operands_a_step(topo, piece):
    """Under an engine mesh (rows on 'data' over the topology's four chips)
    `_tree_combine` halves the array itself, so every cross-device round is
    an all-reduce over PAIRS of devices: a sum of two terms, whose result
    no reduction order can change. The fused spelling would be local sums
    and one all-reduce over all four there, in an order the hardware picks."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from transmogrifai_tpu import histeng
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))

    def shape(dims, dtype):
        spec = PartitionSpec("data", *[None] * (len(dims) - 1))
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))
    fn, args, _ = piece(shape)
    with histeng.engine_mesh(mesh):
        text = jax.jit(fn).lower(*args).compile().as_text()
    groups = _all_reduce_group_sizes(text)
    assert groups, "rows are sharded: the combine must cross devices"
    assert all(0 < g <= 2 for g in groups), groups


def _matrix_reads(text, rows, features):
    """Reads of a ``(rows, features)`` array in one run of a compiled
    module: every scheduled instruction of the entry computation and of the
    loops' bodies with such an operand counts one read an operand, times
    the trip counts of the loops around it (the bound its condition
    compares with). The text names operands without their shapes, so each
    is looked up where it is defined; tuples, bitcasts and the loops
    themselves move no bytes."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            comps[cur].append(line.strip().replace("ROOT ", "").split(
                " = ", 1))
    wanted = re.compile(r"(f32|bf16)\[%d,%d\]" % (rows, features))
    moves_nothing = {"parameter", "get-tuple-element", "tuple", "bitcast",
                     "while", "constant", "copy-start", "copy-done"}

    def reads(comp):
        defined = dict(comps[comp])
        total = 0
        for _, rhs in comps[comp]:
            op = re.match(r"(?:\(.*?\)|\S+) ([\w\-]+)\(", rhs)
            if op.group(1) == "while":
                cond = re.search(r"condition=%?([\w.\-]+)", rhs).group(1)
                body = re.search(r"body=%?([\w.\-]+)", rhs).group(1)
                (trips,) = {int(c) for _, r in comps[cond]
                            for c in re.findall(r"constant\((\d+)\)", r)}
                total += trips * reads(body)
            elif op.group(1) not in moves_nothing:
                operands = rhs[op.end():].split("), ")[0]
                total += sum(
                    bool(wanted.match(defined.get("%" + name, "")))
                    for name in re.findall(r"%([\w.\-]+)", operands))
        return total
    return reads(entry)


@pytest.mark.parametrize("solver,lanes,sweep", [
    ("logreg", 6, True), ("logreg", 1, False),
    ("svc", 3, True), ("svc", 1, False)])
def test_matrix_passes_is_the_compiled_programs_count(topo, solver, lanes,
                                                      sweep):
    """``matrixPasses`` (what ``mesh_refit_roofline`` multiplies a chip's
    rows by) against the binary solvers' programs as the chip's compiler
    makes them under a ``data=4`` mesh: the sweep's lanes in bfloat16 at
    the sweep's schedule, the refit's one float32 lane at the refit's
    (PERF.md Open (k): the attribute was a count of the schedule's
    products, and the compiler fuses three of the refit's into one)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from transmogrifai_tpu.models import linear
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    n, d = 8192, 12

    def shape(dims, spec):
        return jax.ShapeDtypeStruct(dims, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))
    args = [shape((n, d), P("data", None)), shape((n,), P("data")),
            shape((lanes, n), P(None, "data")), shape((lanes,), P())]
    if solver == "logreg":
        newton, cg = linear._LOGREG_STEPS[sweep]
        compiled = linear._fit_logreg_batch.lower(
            *args, shape((lanes,), P()), newton_iters=newton, cg_iters=cg,
            sweep=sweep).compile()
        said = linear.logreg_matrix_passes(sweep)
    else:
        compiled = linear._fit_svc_batch.lower(*args, sweep=sweep).compile()
        said = linear.svc_matrix_passes(sweep)
    assert _matrix_reads(compiled.as_text(), n // 4, d) == said
