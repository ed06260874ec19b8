"""Fused mesh sweep: one sharded XLA program per family
(impl/tuning/validators._make_fused_program mesh branch), on-device fold
masks, the cost-model downgrade, donation safety, and chaos/resume semantics
under the mesh — all on the conftest's 8-virtual-device CPU mesh
(docs/parallel.md).
"""
import os

import numpy as np
import pandas as pd
import pytest
import jax
import jax.numpy as jnp

import transmogrifai_tpu.models.linear   # noqa: F401 (registers families)
import transmogrifai_tpu.models.trees    # noqa: F401
from transmogrifai_tpu.impl.tuning.validators import (
    OpCrossValidation, mesh_program_keys,
)
from transmogrifai_tpu.models.api import MODEL_REGISTRY
from transmogrifai_tpu.parallel import MeshSpec, make_mesh
from transmogrifai_tpu.parallel.mesh import sweep_mesh_decision
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.utils.padding import bucket_for

pytestmark = pytest.mark.mesh

LR_GRID = [{"regParam": r, "elasticNetParam": e}
           for r in (0.01, 0.1, 0.2) for e in (0.0, 0.5)]
SVC_GRID = [{"regParam": 0.01}, {"regParam": 0.1}]


def _synth(n=333, d=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _models(*names_grids):
    return [(MODEL_REGISTRY[n], g) for n, g in names_grids]


@pytest.fixture
def force_mesh(monkeypatch):
    """Pin the mesh on: the test shapes sit far below the cost-model
    thresholds, and these tests target the ENGAGED fused-mesh path."""
    monkeypatch.setenv("TG_MESH_FORCE", "1")


# ---------------------------------------------------------------------------
# fused mesh vs single device: bit-exact winner / params / metrics
# ---------------------------------------------------------------------------

def test_fused_mesh_bit_exact_linear_families(force_mesh):
    """Linear families (one vmapped program, config axis sharded over
    'model', grids traced+donated) must reproduce the single-device fused
    sweep BIT-exactly: same winner, same hyper, identical metric bytes."""
    X, y = _synth()
    models = _models(("OpLogisticRegression", LR_GRID),
                     ("OpLinearSVC", SVC_GRID))
    plain = OpCrossValidation(num_folds=3, seed=7).validate(
        models, X, y, "binary", "AuPR", True, 2)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    sharded = OpCrossValidation(num_folds=3, seed=7, mesh=mesh).validate(
        models, X, y, "binary", "AuPR", True, 2)
    assert sharded.family_name == plain.family_name
    assert sharded.hyper == plain.hyper
    assert sharded.metric_value == plain.metric_value
    for rp, rs in zip(plain.results, sharded.results):
        np.testing.assert_array_equal(rs.fold_metrics, rp.fold_metrics,
                                      err_msg=rp.family)
        np.testing.assert_array_equal(rs.mean_metrics, rp.mean_metrics)


def test_fused_mesh_odd_grid_not_divisible_by_model_axis(force_mesh):
    """F·G = 3·3 = 9 does not divide the model axis (2): the packed grid
    block must pad to the shard multiple and slice in-trace — an unpadded
    block fails device_put outright and silently QUARANTINED the family
    (caught live: SVC's 3-config default grid under a forced mesh)."""
    X, y = _synth()
    models = _models(("OpLinearSVC", [{"regParam": r}
                                      for r in (0.01, 0.1, 0.2)]))
    plain = OpCrossValidation(num_folds=3, seed=7).validate(
        models, X, y, "binary", "AuROC", True, 2)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    sharded = OpCrossValidation(num_folds=3, seed=7, mesh=mesh).validate(
        models, X, y, "binary", "AuROC", True, 2)
    assert not sharded.quarantined
    np.testing.assert_array_equal(sharded.results[0].fold_metrics,
                                  plain.results[0].fold_metrics)
    assert sharded.hyper == plain.hyper


def test_fused_mesh_nonsliced_bit_exact(force_mesh):
    """Full-row masked scoring (fold_sliced=False) under the mesh — the
    shared (n,) label vector is replicated into the config-parallel metric
    stage — also reproduces single-device bytes."""
    X, y = _synth(n=300)
    models = _models(("OpLogisticRegression", LR_GRID))
    plain = OpCrossValidation(num_folds=3, seed=5).validate(
        models, X, y, "binary", "AuROC", True, 2, fold_sliced=False)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    sharded = OpCrossValidation(num_folds=3, seed=5, mesh=mesh).validate(
        models, X, y, "binary", "AuROC", True, 2, fold_sliced=False)
    np.testing.assert_array_equal(sharded.results[0].fold_metrics,
                                  plain.results[0].fold_metrics)


RF_GRID = [{"maxDepth": 3, "minInstancesPerNode": 5, "minInfoGain": 0.001,
            "numTrees": 5, "subsamplingRate": 1.0},
           {"maxDepth": 2, "minInstancesPerNode": 5, "minInfoGain": 0.001,
            "numTrees": 3, "subsamplingRate": 1.0}]
GBT_GRID = [{"maxDepth": 3, "maxIter": 4, "stepSize": 0.3},
            {"maxDepth": 2, "maxIter": 3, "stepSize": 0.1}]


@pytest.mark.hist
@pytest.mark.parametrize("n,d", [(400, 8), (333, 8), (257, 8), (333, 24),
                                 (257, 40)])
def test_fused_mesh_tree_families_bit_exact(force_mesh, n, d):
    """Tree families under the mesh are BIT-identical to single-device —
    the histogram engine's pinned K-blocked reduction (histeng.kernels)
    replaces the order-unspecified psum that used to leave mesh trees only
    'within noise' of the plain sweep. Odd row counts (333, 257) do not
    divide the 'data' axis: bucket padding plus the engine's sentinel row
    blocks must keep the pinned combine identical anyway. At 24 and 40
    columns the forest's per-tree subsets are strict, so its growers run
    the compact per-tree contraction (at 8 they run full width)."""
    X, y = _synth(n=n, d=d)
    rf_attrs = MODEL_REGISTRY["OpRandomForestClassifier"].fit_span_attrs(
        n, d, RF_GRID * 3, 2, True)
    assert (0 < rf_attrs["featSubset"] < d) == (d > 8)
    models = _models(("OpRandomForestClassifier", RF_GRID),
                     ("OpGBTClassifier", GBT_GRID))
    plain = OpCrossValidation(num_folds=3, seed=3).validate(
        models, X, y, "binary", "AuROC", True, 2)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    sharded = OpCrossValidation(num_folds=3, seed=3, mesh=mesh).validate(
        models, X, y, "binary", "AuROC", True, 2)
    assert sharded.family_name == plain.family_name
    assert sharded.hyper == plain.hyper
    assert sharded.metric_value == plain.metric_value
    for rp, rs in zip(plain.results, sharded.results):
        np.testing.assert_array_equal(rs.fold_metrics, rp.fold_metrics,
                                      err_msg=rp.family)
        np.testing.assert_array_equal(rs.mean_metrics, rp.mean_metrics)


def test_the_sweep_span_says_which_combine_the_program_traced(force_mesh):
    """``combine`` on a tree family's ``sweep.family`` span is read under
    the context its program is traced in: ``"fused"`` for the plain sweep,
    ``"halving"`` for the mesh one (two-operand cross-device steps)."""
    from transmogrifai_tpu.observability import trace as obs
    X, y = _synth(n=400, d=8)
    models = _models(("OpGBTClassifier", GBT_GRID))
    seen = []
    obs.enable_tracing(True)
    try:
        for mesh in (None, make_mesh(MeshSpec(data=4, model=2))):
            obs.tracer().clear()
            OpCrossValidation(num_folds=3, seed=3, mesh=mesh).validate(
                models, X, y, "binary", "AuROC", True, 2)
            seen += [(s.attrs["histShards"], s.attrs["combine"])
                     for s in obs.tracer().finished()
                     if s.name == "sweep.family"]
    finally:
        obs.enable_tracing(False)
    assert seen == [(8, "fused"), (8, "halving")]


# ---------------------------------------------------------------------------
# on-device fold masks == the eager (F, n) tensors they replaced
# ---------------------------------------------------------------------------

def test_on_device_fold_masks_match_eager_tensors():
    """The fused program derives train-weights/val-masks from the uint8
    fold-id vector INSIDE the trace; the round-5 mesh path assembled (F, n)
    tensors eagerly. Both constructions are integer/boolean — they must be
    bit-identical, including bucket padding (id F+1: never train, never
    validate) and TVS train-only rows (id F: train everywhere, validate
    nowhere)."""
    n, F = 333, 3
    rng = np.random.RandomState(7)
    vm = np.zeros((F, n), bool)
    perm = rng.permutation(n)
    # leave a tail of train-only rows (the TVS shape)
    for f in range(F):
        vm[f, perm[f::F][:40]] = True
    fold_ids = np.where(vm.any(axis=0), vm.argmax(axis=0), F).astype(np.uint8)
    n_pad = bucket_for(n, multiple_of=4)
    ids = np.pad(fold_ids, (0, n_pad - n), constant_values=F + 1)

    # eager reference (pre-change mesh path): mask-built tensors
    f_iota = np.arange(F, dtype=np.uint8)[:, None]
    train_eager = (ids[None, :] != f_iota).astype(np.float32)
    train_eager[:, n:] = 0.0                       # pad rows carried 0 weight
    val_eager = ids[None, :] == f_iota

    # in-trace construction (exactly _make_fused_program's expressions)
    ids_d = jnp.asarray(ids)

    @jax.jit
    def build(ids_d):
        fi = jnp.arange(F, dtype=jnp.uint8)[:, None]
        train = ((ids_d[None, :] != fi)
                 & (ids_d[None, :] != jnp.uint8(F + 1))).astype(jnp.float32)
        val = ids_d[None, :] == fi
        return train, val

    train_dev, val_dev = build(ids_d)
    np.testing.assert_array_equal(np.asarray(train_dev), train_eager)
    np.testing.assert_array_equal(np.asarray(val_dev), val_eager)


# ---------------------------------------------------------------------------
# cost-model downgrade
# ---------------------------------------------------------------------------

def test_downgrade_boundaries(monkeypatch):
    mesh = make_mesh(MeshSpec(data=4, model=2))
    monkeypatch.setenv("TG_MESH_MIN_ROWS_PER_CHIP", "1000")
    monkeypatch.setenv("TG_MESH_MIN_CONFIGS_PER_CHIP", "4")
    # exactly at both thresholds → engage
    assert sweep_mesh_decision(mesh, 4000, 8)[0]
    # one row below the per-chip floor → downgrade
    engage, detail = sweep_mesh_decision(mesh, 3999, 8)
    assert not engage and detail["rowsPerChip"] < 1000
    # configs below the model-shard floor → downgrade
    assert not sweep_mesh_decision(mesh, 4000, 7)[0]
    # a zeroed threshold disables that axis of the check
    monkeypatch.setenv("TG_MESH_MIN_CONFIGS_PER_CHIP", "0")
    assert sweep_mesh_decision(mesh, 4000, 1)[0]
    # force wins over everything
    monkeypatch.setenv("TG_MESH_MIN_ROWS_PER_CHIP", "10**9")
    monkeypatch.setenv("TG_MESH_FORCE", "1")
    assert sweep_mesh_decision(mesh, 1, 1)[0]


def test_downgraded_sweep_is_bit_identical_and_observable():
    """Below-threshold sweeps run the single-device fused path byte-for-byte
    and record the decision (counter + span event)."""
    from transmogrifai_tpu.observability import metrics as obs_metrics
    from transmogrifai_tpu.observability import trace as obs_trace

    X, y = _synth()  # 333 rows: far below the default rows-per-chip floor
    models = _models(("OpLogisticRegression", LR_GRID))
    plain = OpCrossValidation(num_folds=3, seed=7).validate(
        models, X, y, "binary", "AuPR", True, 2)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    obs_trace.enable_tracing(True)
    try:
        down = OpCrossValidation(num_folds=3, seed=7, mesh=mesh).validate(
            models, X, y, "binary", "AuPR", True, 2)
        snap = obs_metrics.registry().snapshot()
        assert sum(snap.get("tg_mesh_downgrade_total", {}).values()) == 1
        names = [s.name for s in obs_trace.tracer().finished()]
        assert "sweep.mesh_downgrade" in names
    finally:
        obs_trace.enable_tracing(None)
    np.testing.assert_array_equal(down.results[0].fold_metrics,
                                  plain.results[0].fold_metrics)
    assert down.hyper == plain.hyper
    # no mesh-compiled program was built for the downgraded sweep
    assert not mesh_program_keys()


def test_downgraded_sweep_gathers_sharded_rows_onto_one_device(monkeypatch):
    """A downgraded sweep is single-device for its INPUTS too. Rows that an
    upstream mesh stage left sharded would turn the fused one-device program
    into a GSPMD program; on the chip a tree family's Mosaic kernels (traced
    with no engine mesh) then refuse to lower — "Mosaic kernels cannot be
    automatically partitioned", PR 21's four-chip run of a 3-fit forest
    sweep."""
    from collections import OrderedDict

    from jax.sharding import NamedSharding, PartitionSpec as P

    from transmogrifai_tpu.impl.tuning import validators as V

    X, y = _synth(n=332)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
    ys = jax.device_put(y, NamedSharding(mesh, P("data")))
    seen = []
    build = V._make_fused_program

    def spying_build(*a, **kw):
        prog, grid_keys = build(*a, **kw)

        def spy(*args):
            seen.extend(len(x.sharding.device_set) for x in args)
            return prog(*args)
        return spy, grid_keys
    monkeypatch.setattr(V, "_make_fused_program", spying_build)
    monkeypatch.setattr(V, "_FUSED_CACHE", OrderedDict())   # force a build
    models = _models(("OpLogisticRegression", LR_GRID))
    down = OpCrossValidation(num_folds=3, seed=7, mesh=mesh).validate(
        models, Xs, ys, "binary", "AuPR", True, 2)
    assert seen and set(seen) == {1}
    plain = OpCrossValidation(num_folds=3, seed=7).validate(
        models, X, y, "binary", "AuPR", True, 2)
    np.testing.assert_array_equal(down.results[0].fold_metrics,
                                  plain.results[0].fold_metrics)


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

def test_grid_donation_no_use_after_donate(force_mesh):
    """The packed per-family grid block is donated into the fused program:
    the validator must upload a FRESH block per dispatch (repeat calls stay
    correct) and the donated buffer must actually be consumed — holding a
    reference and reading it back after the call is an error by design."""
    X, y = _synth()
    mesh = make_mesh(MeshSpec(data=4, model=2))
    cv = OpCrossValidation(num_folds=3, seed=7, mesh=mesh)
    models = _models(("OpLogisticRegression", LR_GRID))
    first = cv.validate(models, X, y, "binary", "AuPR", True, 2)
    second = cv.validate(models, X, y, "binary", "AuPR", True, 2)
    np.testing.assert_array_equal(first.results[0].fold_metrics,
                                  second.results[0].fold_metrics)

    # direct probe of the donation contract on the compiled program
    from transmogrifai_tpu.impl.tuning import validators as V
    keys = mesh_program_keys()
    assert keys, "forced mesh sweep should compile mesh-keyed programs"
    fam = MODEL_REGISTRY["OpLogisticRegression"]
    assert getattr(fam, "traced_grid_ok", False)


def test_donated_grid_buffer_is_consumed(force_mesh):
    """The grid block is handed to the program with donate_argnums: either
    XLA aliased it (reading it back raises — the usual accelerator case) or
    XLA declined the alias (tiny CPU buffers) and the block must be byte-
    unchanged — donation must never silently clobber a still-readable
    input."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from transmogrifai_tpu.impl.tuning.validators import _make_fused_program
    fam = MODEL_REGISTRY["OpLogisticRegression"]
    mesh = make_mesh(MeshSpec(data=4, model=2))
    F, grid = 2, LR_GRID
    G = len(grid)
    garr = {k: np.asarray(v) for k, v in fam.grid_to_arrays(grid).items()}
    prog, gkeys = _make_fused_program(
        fam, garr, G, F, "binary", "AuROC", 2, False, False, None,
        mesh=mesh, x_ndim=2)
    assert gkeys is not None
    n = 256
    X, y = _synth(n=n)
    ids = np.zeros(n, np.uint8)
    ids[n // 2:] = 1
    gb_host = np.stack([np.tile(garr[k], F) for k in gkeys]
                       ).astype(np.float32)
    gb = jax.device_put(jnp.asarray(gb_host),
                        NamedSharding(mesh, P(None, "model")))
    m = prog(X, y, jnp.asarray(ids), gb)
    np.asarray(m)  # sync
    try:
        back = np.asarray(gb)
    except RuntimeError:
        return  # donated buffer consumed — the accelerator contract
    np.testing.assert_array_equal(back, gb_host)


# ---------------------------------------------------------------------------
# chaos + resume semantics under the mesh (PR 1–2 byte-preservation)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_family_quarantine_under_mesh(force_mesh):
    """An armed validator.family_fit fault under the mesh quarantines that
    family and the sweep continues on the rest — same semantics, same
    records, as the single-device path."""
    X, y = _synth()
    models = _models(("OpLogisticRegression", LR_GRID),
                     ("OpLinearSVC", SVC_GRID))
    mesh = make_mesh(MeshSpec(data=4, model=2))
    spec = {"validator.family_fit": {"mode": "raise",
                                     "key": "OpLogisticRegression"}}
    with faults.injected(spec):
        best_mesh = OpCrossValidation(num_folds=3, seed=7,
                                      mesh=mesh).validate(
            models, X, y, "binary", "AuPR", True, 2)
    with faults.injected(spec):
        best_plain = OpCrossValidation(num_folds=3, seed=7).validate(
            models, X, y, "binary", "AuPR", True, 2)
    assert best_mesh.family_name == best_plain.family_name == "OpLinearSVC"
    q_mesh = {q["family"] for q in best_mesh.quarantined}
    q_plain = {q["family"] for q in best_plain.quarantined}
    assert q_mesh == q_plain and "OpLogisticRegression" in q_mesh
    lr_m = next(r for r in best_mesh.results
                if r.family == "OpLogisticRegression")
    assert np.all(np.isnan(lr_m.fold_metrics))


@pytest.mark.chaos
def test_preempt_sweep_resume_under_mesh(tmp_path, monkeypatch):
    """Kill the train at preempt.sweep with the sweep running under a
    FORCED mesh, resume, and reproduce the uninterrupted mesh run's winner
    + metrics — preemption propagation and sweep-checkpoint replay
    (PRs 1–2) must survive the fused mesh path byte-for-byte."""
    from transmogrifai_tpu.features import reset_uids
    from transmogrifai_tpu.robustness.faults import SimulatedPreemption
    from transmogrifai_tpu.workflow import OpWorkflow

    monkeypatch.setenv("TG_MESH_FORCE", "1")
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)

    rng = np.random.RandomState(7)
    n = 300
    x1, x2 = rng.randn(n), rng.randn(n)
    df = pd.DataFrame({"x1": x1, "x2": x2,
                       "y": ((x1 + 0.5 * x2) > 0).astype(float)})
    models = [("OpLogisticRegression", LR_GRID[:2]),
              ("OpLinearSVC", [{"regParam": 0.01}])]

    def _pred():
        label = FeatureBuilder.RealNN("y").extract_field().as_response()
        f1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
        f2 = FeatureBuilder.Real("x2").extract_field().as_predictor()
        checked = tg.transmogrify([f1, f2]).sanity_check(label)
        return (BinaryClassificationModelSelector.with_cross_validation(
            models=models).set_input(label, checked).get_output())

    mesh = make_mesh(MeshSpec(data=4, model=2))

    reset_uids()
    base_pred = _pred()
    base = (OpWorkflow().set_input_dataset(df).set_result_features(base_pred)
            .with_mesh(mesh).train())

    ck = str(tmp_path / "ckpt")
    reset_uids()
    pred1 = _pred()
    with faults.injected({"preempt.sweep": {"mode": "preempt", "nth": 2}}):
        with pytest.raises(SimulatedPreemption):
            (OpWorkflow().set_input_dataset(df).set_result_features(pred1)
             .with_mesh(mesh).with_checkpoint_dir(ck).train())

    reset_uids()
    pred2 = _pred()
    model = (OpWorkflow().set_input_dataset(df).set_result_features(pred2)
             .with_mesh(mesh).with_checkpoint_dir(ck).train(resume=True))
    assert model.summary()["resume"]["restoredSweepCandidates"]

    def _sel(m):
        return next(v for k, v in m.summary().items()
                    if k != "faults" and isinstance(v, dict)
                    and "bestModelType" in v)
    b, r = _sel(base), _sel(model)
    assert r["bestModelType"] == b["bestModelType"]
    assert r["bestHyperparameters"] == b["bestHyperparameters"]
    np.testing.assert_allclose(r["bestMetricValue"], b["bestMetricValue"],
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(model.score(df=df)[pred2.name].values),
        np.asarray(base.score(df=df)[base_pred.name].values), atol=1e-6)


# ---------------------------------------------------------------------------
# packed sharded table upload
# ---------------------------------------------------------------------------

def test_shard_table_packed_uploads_and_layout():
    """shard_table moves ALL device-kind columns in ≤2 sharded transfers
    (one value block + one mask block) and every resulting column is a
    row-sharded on-device view with bit-identical values/masks."""
    from transmogrifai_tpu.observability import metrics as obs_metrics
    from transmogrifai_tpu.parallel import shard_table
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import Real, Text

    rng = np.random.RandomState(0)
    n = 333
    cols = {
        "a": Column(Real, rng.randn(n).astype(np.float32), rng.rand(n) > .2),
        "b": Column(Real, rng.randn(n).astype(np.float32), None),
        "t": Column(Text, np.asarray(["s%d" % i for i in range(n)],
                                     dtype=object), None),
    }
    table = FeatureTable(dict(cols), n)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    obs_metrics.enable_metrics(True)
    try:
        before = obs_metrics.registry().snapshot().get(
            "tg_device_transfer_total", {})
        n_before = sum(before.values()) if before else 0.0
        sharded = shard_table(table, mesh)
        snap = obs_metrics.registry().snapshot()
        n_after = sum(snap["tg_device_transfer_total"].values())
        assert n_after - n_before <= 2
        tbytes = sum(snap.get("tg_transfer_bytes_total", {}).values())
        assert tbytes > 0
    finally:
        obs_metrics.enable_metrics(None)
    assert sharded.num_rows == 336                     # padded to 4·84
    for name in ("a", "b"):
        got = np.asarray(sharded[name].values)
        np.testing.assert_array_equal(got[:n], np.asarray(cols[name].values))
        assert np.all(got[n:] == 0)
        mask = np.asarray(sharded[name].mask)
        np.testing.assert_array_equal(
            mask[:n],
            np.ones(n, bool) if cols[name].mask is None
            else np.asarray(cols[name].mask))
        assert not mask[n:].any()
        assert "data" in str(sharded[name].values.sharding)
    # object column padded with None, host-resident
    assert sharded["t"].values[n] is None


def test_no_mesh_program_leak_fixture_probe():
    """Companion to the conftest no-leak fixture: compiling a mesh program
    registers a mesh-keyed cache entry; the fixture clears it after each
    test, so entry here must be clean."""
    assert not mesh_program_keys()


# ---------------------------------------------------------------------------
# the whole train under with_mesh(data=4): equal to one device, and no
# array of row size whole on a device (PR 34)
# ---------------------------------------------------------------------------

FAMILY_GRIDS = {
    "OpLogisticRegression": LR_GRID[:2],
    "OpLinearSVC": SVC_GRID,
    "OpRandomForestClassifier": [{"maxDepth": 3, "numTrees": 4}],
    "OpGBTClassifier": [{"maxDepth": 3, "maxIter": 4}],
}


def _mesh_table(n=4000, seed=5):
    rng = np.random.RandomState(seed)
    x1, x2, x3 = rng.randn(n), rng.randn(n), rng.rand(n)
    shop = rng.choice([f"s{i}" for i in range(6)], n)
    z = x1 - 0.5 * x2 + 0.8 * (shop == "s1") + 0.3 * rng.randn(n)
    return pd.DataFrame({"x1": x1, "x2": x2, "x3": x3, "shop": shop,
                         "y": (z > 0).astype(float)})


def _train(df, family, mesh=None):
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.features import reset_uids
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.impl.selector.model_selector import SelectedModel
    from transmogrifai_tpu.workflow import OpWorkflow
    reset_uids()
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
             for c in ("x1", "x2", "x3")]
    feats.append(FeatureBuilder.PickList("shop").extract_field()
                 .as_predictor())
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        models=[(family, FAMILY_GRIDS[family])])
        .set_input(label, checked).get_output())
    wf = OpWorkflow().set_input_dataset(df).set_result_features(pred)
    if mesh is not None:
        wf = wf.with_mesh(mesh)
    model = wf.train()
    return next(s for s in model.stages if isinstance(s, SelectedModel))


def _whole_on_a_device(arr, n_data=4):
    """Why ``arr`` is not shared evenly over the data axis, or None."""
    if not isinstance(arr, jax.Array):
        return f"a host array {type(arr).__name__}"
    rows = arr.shape[-2] if arr.ndim > 2 else arr.shape[0]
    axis = arr.ndim - 2 if arr.ndim > 2 else 0
    got = sorted({s.data.shape[axis] for s in arr.addressable_shards})
    if len(arr.sharding.device_set) != n_data or got != [rows // n_data]:
        return (f"{arr.shape} lies in shards of {got} rows on "
                f"{len(arr.sharding.device_set)} device(s)")
    return None


@pytest.mark.parametrize("family", sorted(FAMILY_GRIDS))
def test_with_mesh_train_equals_one_device_and_shares_every_row_array(
        force_mesh, monkeypatch, family):
    """The train a user runs, ``OpWorkflow.with_mesh(data=4).train()``, for
    each binary family alone: the winner, every fold metric and the refit's
    parameters are the one-device train's (a tree family's to the bit: the
    pinned combine; a linear family's to float32 rounding, since its row
    sums are taken a chip at a time: docs/parallel.md), and no array with
    the table's rows is committed whole to one device: the combiner's
    output, the refit's ``Xf`` and ``W``, the evaluation's ``X``."""
    from transmogrifai_tpu.impl.feature.vectorizers import VectorsCombiner
    fam = MODEL_REGISTRY[family]
    df = _mesh_table()
    plain = _train(df, family)

    seen = {}
    real_fit, real_parts = type(fam).fit_batch, type(fam).predict_parts
    real_combine = VectorsCombiner.transform_column

    def fit_batch(self, X, y, W, *a, **kw):
        if not isinstance(X, jax.core.Tracer):      # the refit, not a trace
            seen["refit Xf"], seen["refit W"] = X, W[0]
        return real_fit(self, X, y, W, *a, **kw)

    def predict_parts(self, fitted, X):
        if not isinstance(X, jax.core.Tracer) and X.shape[0] > 1000:
            seen.setdefault("evaluation X", X)
        return real_parts(self, fitted, X)

    def transform_column(self, table):
        col = real_combine(self, table)
        if table.num_rows == len(df):
            seen["combiner output"] = col.values
        return col

    monkeypatch.setattr(type(fam), "fit_batch", fit_batch)
    monkeypatch.setattr(type(fam), "predict_parts", predict_parts)
    monkeypatch.setattr(VectorsCombiner, "transform_column", transform_column)
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    sharded = _train(df, family, mesh)

    assert set(seen) == {"combiner output", "refit Xf", "refit W",
                         "evaluation X"}
    for what, arr in seen.items():
        assert _whole_on_a_device(arr) is None, (
            what, _whole_on_a_device(arr))

    sp, ss = plain.summary, sharded.summary
    assert (ss.best_model_type, ss.best_hyper) == (sp.best_model_type,
                                                   sp.best_hyper)
    trees = getattr(fam, "uses_hist_engine", False)
    tol = dict(rtol=0, atol=0) if trees else dict(rtol=2e-4, atol=2e-5)
    for rp, rs in zip(sp.validation_results, ss.validation_results):
        np.testing.assert_allclose(rs.fold_metrics, rp.fold_metrics,
                                   err_msg=rp.family, **tol)
    assert set(sharded.fitted.params) == set(plain.fitted.params)
    for k, v in plain.fitted.params.items():
        np.testing.assert_allclose(
            np.asarray(sharded.fitted.params[k]), np.asarray(v),
            err_msg=k, **(tol if trees else dict(rtol=1e-3, atol=1e-4)))
    for k, v in sp.holdout_evaluation.items():
        assert ss.holdout_evaluation[k] == pytest.approx(
            v, abs=1e-4, nan_ok=True), k


def test_take_rows_is_the_rows_own_bits_shard_to_shard():
    """``parallel.sharded.take_rows``: ``X[idx]`` of a row-sharded table,
    the result's rows sharded alike; -1 gives a row of zeros; a last step
    that overlaps the one before; a table whose rows do not divide."""
    from transmogrifai_tpu.parallel import sharded as S
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    rng = np.random.RandomState(0)
    X = rng.randn(1003, 7).astype(np.float32)
    X[5, 2] = -0.0
    Xs = S.place_rows(X[:1000], mesh)
    assert _whole_on_a_device(Xs) is None
    for m, block in ((360, 64), (360, 65536), (4, 3), (2000, 77)):
        idx = rng.randint(0, 1000, size=m)
        idx[::9] = -1
        out = S.take_rows(Xs, idx, mesh, block=block)
        assert out.sharding == S.row_sharding(mesh, 2)
        want = np.where((idx >= 0)[:, None], X[np.maximum(idx, 0)], 0.0)
        np.testing.assert_array_equal(np.asarray(out), want)
    odd = S.take_rows(jnp.asarray(X), np.arange(1003, 1003 - 8, -1) - 1,
                      mesh)
    np.testing.assert_array_equal(np.asarray(odd), X[::-1][:8])
    # a vector, and a mesh with a 'model' axis beside 'data'
    both = make_mesh(MeshSpec(data=4, model=2))
    vec = S.take_rows(S.place_rows(X[:1000, 0].copy(), both),
                      np.arange(8), both)
    np.testing.assert_array_equal(np.asarray(vec), X[:8, 0])
    padded = S.pad_rows_sharded(Xs, 1024, mesh)
    assert _whole_on_a_device(padded) is None
    np.testing.assert_array_equal(np.asarray(padded)[:1000], X[:1000])
    assert not np.asarray(padded)[1000:].any()
    with pytest.raises(ValueError):
        S.take_rows(Xs, np.arange(6), mesh)


@pytest.mark.parametrize("rows, fault", [
    (None, None), ("bucket", None), ("bucket", "more than X has"),
    ("bucket", "fewer than y"), ("odd", "not the data axis's")])
def test_validate_takes_a_table_already_at_its_bucket_only_when_told(
        force_mesh, rows, fault):
    """``padded_rows`` says that ``X`` came padded (zeros past ``len(y)``):
    the sweep then reads it as it stands, equal to the sweep that pads by
    itself; a size that is not ``X``'s, under ``len(y)`` or not a multiple
    of the data axis is refused, never guessed from the shapes."""
    from transmogrifai_tpu.parallel import sharded as S
    X, y = _synth(n=333)
    models = _models(("OpLogisticRegression", LR_GRID[:2]))
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    cv = OpCrossValidation(num_folds=3, seed=7, mesh=mesh)
    plain = cv.validate(models, X, y, "binary", "AuPR", True, 2)
    if rows is None:         # told nothing, the sweep pads to its bucket itself
        assert sum(shape[0] for _, shape in cv.last_sweep_shards) \
            == bucket_for(333, 4)
        return
    n_b = bucket_for(333, 4) if rows == "bucket" else 335
    Xp = S.pad_rows_sharded(X, n_b, mesh) if rows == "bucket" \
        else jnp.pad(X, ((0, 2), (0, 0)))
    if fault is None:
        got = cv.validate(models, Xp, y, "binary", "AuPR", True, 2,
                          padded_rows=n_b)
        assert got.hyper == plain.hyper
        for rp, rg in zip(plain.results, got.results):
            np.testing.assert_array_equal(rg.fold_metrics, rp.fold_metrics)
        assert cv.last_sweep_shards and sum(
            shape[0] for _, shape in cv.last_sweep_shards) == n_b
        return
    say = {"more than X has": 2 * n_b, "fewer than y": 332,
           "not the data axis's": 335}[fault]
    with pytest.raises(ValueError, match="padded_rows"):
        cv.validate(models, Xp if fault != "fewer than y" else X[:332],
                    y, "binary", "AuPR", True, 2, padded_rows=say)


def test_the_spans_say_what_the_mesh_did(force_mesh):
    """``mesh.place`` around each sharded upload; ``sweep.family``,
    ``selector.refit`` and ``selector.evaluate`` carry the mesh, whether it
    engaged and the rows a chip reads; ``workflow.train`` the chips."""
    from transmogrifai_tpu.observability import trace as obs_trace
    df = _mesh_table(n=2000)
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    obs_trace.enable_tracing(True)
    _train(df, "OpRandomForestClassifier", mesh)
    spans = {}
    for s in obs_trace.tracer().finished():
        spans.setdefault(s.name, []).append(s)
    places = spans["mesh.place"]
    assert {p.attrs["path"] for p in places} == {"host_shards"}
    assert all(p.attrs["shards"] == 4 and p.attrs["bytes"] > 0
               for p in places)
    assert max(p.attrs["bytes"] for p in places) >= 2000 * 4 * 4
    (fam,) = spans["sweep.family"]
    assert (fam.attrs["meshData"], fam.attrs["meshModel"],
            fam.attrs["engaged"]) == (4, 1, True)
    # a tree family's sweep fit reads its sample, not the table
    assert fam.attrs["rowsPerChip"] == fam.attrs["sampleRows"] // 4
    (refit,) = spans["selector.refit"]
    assert refit.attrs["engaged"] is True and refit.attrs["meshData"] == 4
    assert refit.attrs["rowsPerChip"] == bucket_for(1800, 4) // 4
    (ev,) = spans["selector.evaluate"]
    assert ev.attrs["rowsPerChip"] == 2000 // 4
    assert spans["workflow.train"][0].attrs["chips"] == 4
    # every shard-to-shard gather says what a chip receives, and where
    takes = {t.attrs["site"]: t.attrs for t in spans["mesh.take_rows"]}
    assert set(takes) == {"selector.prepare", "selector.evaluate"}
    for a in takes.values():
        assert a["shards"] == 4
        assert a["rowBytes"] == 4 * refit.attrs["features"]
        assert a["rowsPerChip"] * 4 == a["rows"]
        assert a["steps"] == 1
    assert takes["selector.prepare"]["rows"] == bucket_for(1800, 4)
    obs_trace.tracer().clear()
    _train(df, "OpLogisticRegression")
    by = {s.name: s for s in obs_trace.tracer().finished()}
    assert "mesh.place" not in by and "mesh.take_rows" not in by
    assert (by["sweep.family"].attrs["meshData"],
            by["sweep.family"].attrs["engaged"]) == (1, False)
    assert by["sweep.family"].attrs["matrixPasses"] == 5 + 8 * (4 + 2 * 6)
    assert by["selector.refit"].attrs["matrixPasses"] == 4 + 10 * (3 + 16)
    assert by["workflow.train"].attrs["chips"] == 1


# ---------------------------------------------------------------------------
# the pivot's block and the combined matrix are born sharded (PR 35)
# ---------------------------------------------------------------------------

def _pivot_and_combine(table, mesh, reals_to=None):
    from transmogrifai_tpu import FeatureBuilder, FeatureTable
    from transmogrifai_tpu.table import Column
    from transmogrifai_tpu.impl.feature.vectorizers import (
        OneHotVectorizer, VectorsCombiner)
    picks = [FeatureBuilder.PickList(c).extract_field().as_predictor()
             for c in ("shop", "day")]
    reals = FeatureBuilder.OPVector("reals").extract_field().as_predictor()
    pivot = OneHotVectorizer(min_support=1)
    pivot.set_input(*picks)
    comb = VectorsCombiner()
    comb.set_input(pivot.get_output(), reals)
    if mesh is not None:
        pivot.set_mesh(mesh)
        comb.set_mesh(mesh)
    model = pivot.fit(table)
    block = model.transform_column(table)
    reals = table["reals"]
    if reals_to is not None:            # already on a device
        reals = Column(reals.feature_type,
                       jax.device_put(reals.values, reals_to), None)
    out = comb.transform_column(FeatureTable(
        {pivot.get_output().name: block, "reals": reals}, table.num_rows))
    return block, out


def _pick_table(n, seed=9):
    from transmogrifai_tpu import FeatureTable
    from transmogrifai_tpu.table import Column
    from transmogrifai_tpu.types import OPVector, PickList
    rng = np.random.RandomState(seed)
    shop = np.array([f"s{i}" for i in range(6)] + [None], dtype=object)[
        rng.randint(0, 7, n)]
    day = np.array(list("mtwTf"), dtype=object)[rng.randint(0, 5, n)]
    return FeatureTable({
        "shop": Column(PickList, shop, np.array([v is not None
                                                 for v in shop])),
        "day": Column(PickList, day, np.ones(n, bool)),
        "reals": Column(OPVector, rng.rand(n, 3).astype(np.float32), None),
    }, n)


@pytest.mark.parametrize("n", [4000, 4001], ids=["divides", "odd"])
def test_the_pivot_block_and_the_combined_matrix_are_born_sharded(
        monkeypatch, n):
    """Under ``data=4`` the positions go up as shards, the two programs
    write shards and no device holds a whole copy of the positions, the
    block or the matrix; the bits are the one-device host path's. A row
    count the data axis does not divide stays on one device and still
    gives the right matrix."""
    from transmogrifai_tpu.impl.feature import vectorizers
    from transmogrifai_tpu.parallel.sharded import row_sharding
    table = _pick_table(n)
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 10 ** 12)
    want_block, want = _pivot_and_combine(table, None)
    assert isinstance(want_block.values, np.ndarray)
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 1000)
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    before = {id(a) for a in jax.live_arrays()}
    went_up = []            # held, so that the uploads are alive below
    real = vectorizers._upload

    def upload(host, mesh, site):
        went_up.append(real(host, mesh, site))
        return went_up[-1]
    monkeypatch.setattr(vectorizers, "_upload", upload)
    block, out = _pivot_and_combine(table, mesh)
    mine = [a for a in jax.live_arrays()
            if id(a) not in before and a.ndim and a.shape[0] >= n // 2]
    # two columns' positions, the reals, the block, the matrix
    assert sorted(a.shape for a in mine) == [
        (n,), (n,), (n, 3), (n, 8 + 7), (n, 8 + 7 + 3)]
    if n % 4 == 0:
        for a in (block.values, out.values):
            assert a.sharding.is_equivalent_to(row_sharding(mesh, 2), 2)
        for a in mine:
            assert _whole_on_a_device(a) is None, _whole_on_a_device(a)
    else:
        assert len(out.values.sharding.device_set) == 1
    np.testing.assert_array_equal(np.asarray(block.values),
                                  want_block.values)
    np.testing.assert_array_equal(np.asarray(out.values),
                                  np.asarray(want.values))
    assert (out.metadata["vector_meta"].column_names()
            == want.metadata["vector_meta"].column_names())
    if n % 4 == 0:
        # an input that lies whole on one chip is re-placed, chip to chips
        _, again = _pivot_and_combine(table, mesh, jax.devices()[0])
        assert _whole_on_a_device(again.values) is None
        np.testing.assert_array_equal(np.asarray(again.values),
                                      np.asarray(want.values))


def test_a_second_train_under_the_mesh_builds_no_program(force_mesh,
                                                         monkeypatch):
    """The pivot's and the combiner's programs are keyed by shape, widths
    and sharding: a second ``train()`` on the same table finds every
    program (the counter ``compiles_in_window`` reads)."""
    from benchmark.harness import COMPILE_EVENT, Monitor
    from transmogrifai_tpu.impl.feature import vectorizers
    monkeypatch.setattr(vectorizers, "_DEVICE_BLOCK_MIN_ROWS", 1000)
    df = _mesh_table()
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    seen = []
    real = vectorizers._pivot_block

    def spy(positions, widths, mesh):
        seen.append((len(positions), widths, mesh is not None))
        return real(positions, widths=widths, mesh=mesh)
    monkeypatch.setattr(vectorizers, "_pivot_block", spy)
    monitor = Monitor().install()
    first = _train(df, "OpLogisticRegression", mesh)
    monitor.phase = "second"
    second = _train(df, "OpLogisticRegression", mesh)
    monitor.phase = "after"
    assert seen == [(1, (6 + 2,), True)] * 2        # the device path ran
    assert monitor.count("setup", COMPILE_EVENT) > 0
    assert monitor.count("second", COMPILE_EVENT) == 0
    for k, v in first.fitted.params.items():
        np.testing.assert_array_equal(np.asarray(second.fitted.params[k]),
                                      np.asarray(v))
