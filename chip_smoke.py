"""Chip smoke: train -> score -> serve on the TPU through the normal entry
points, plus the five Pallas kernels against their XLA twins.

    python chip_smoke.py [--seed N]

The quickest proof that the system still starts on the chip. One process,
data made from ``--seed``, no network. The shape is the repository's
documented "Full AutoML train": 12 ``Real`` + 2 ``PickList`` (5 and 40
levels) predictors -> ``transmogrify`` -> ``sanity_check`` ->
``BinaryClassificationModelSelector.with_cross_validation()`` with the
stock default grids (135 fits) -> ``OpWorkflow.train()``; then
``model.score()``, ``model.save()`` -> ``ModelRegistry.load()`` -> single-row
``submit()`` calls. With four or more devices the train and score run a
second time under a ``data=4`` mesh and must agree with the first.

Exits non-zero unless ``jax.default_backend() == "tpu"``. No phase is
wrapped in a ``try`` that lets the script reach exit 0: any exception, and
any non-empty fault section, fails the run. The walls it prints are smoke
timings, not metrics. A passing run ends with two JSON lines on stdout: the
summary (phases, walls, compile-cache counts, ``"claim": null``) and, last,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

ROWS = 1_000_000          # train rows (the documented full-train shape)
HOLDOUT_ROWS = 100_000    # held-out rows for the AuROC floor
PARITY_ROWS = 10_000      # planned-vs-eager slice
SERVE_REQUESTS = 1_000
AUROC_FLOOR = 0.95        # the label is a noisy linear rule: ~0.99 reachable
DEFAULT_GRID_FITS = 135   # (6 LR + 18 RF + 18 GBT + 3 SVC) x 3 folds
#: single-chip vs mesh agreement (mesh tree fits run the XLA contraction)
MESH_AUROC_TOL = 0.005
MESH_TIE_TOL = 1e-3

#: FaultLog kinds that must never appear in a bring-up run
_FAULT_KINDS = ("quarantine", "retry", "plan_fallback", "oom_downshift",
                "breaker_degraded", "fatal", "aot_fallback")
_SUMMARY_FAULT_KEYS = ("quarantined", "retries", "planFallbacks",
                       "oomDownshifts", "breakerDegraded", "fatal")


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def make_data(n: int, seed: int) -> Dict[str, np.ndarray]:
    """1 M-row binary table of 12 reals, two categoricals and derived
    columns, built in bulk from the seed."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 12).astype(np.float32)
    c1 = rng.choice(["a", "b", "c", "d", "e"], size=n)
    c2 = rng.choice([f"k{i}" for i in range(40)], size=n)
    y = (X[:, 0] - X[:, 1] + (c1 == "a") + 0.3 * rng.randn(n)
         > 0).astype(np.float32)
    data = {f"x{i}": X[:, i].copy() for i in range(12)}
    data.update(c1=c1.astype(object), c2=c2.astype(object), label=y)
    return data


def table_of(data: Dict[str, np.ndarray], lo: int, hi: int):
    from transmogrifai_tpu.table import Column, FeatureTable
    from transmogrifai_tpu.types import PickList, Real, RealNN

    n = hi - lo
    valid = np.ones(n, dtype=bool)
    cols = {}
    for name, arr in data.items():
        ftype = (RealNN if name == "label"
                 else PickList if name in ("c1", "c2") else Real)
        cols[name] = Column(ftype, arr[lo:hi], valid)
    return FeatureTable(cols, n)


def rows_of(data: Dict[str, np.ndarray], lo: int, hi: int
            ) -> List[Dict[str, Any]]:
    """Request rows (python values, label left out) for the serve phase."""
    names = [k for k in data if k != "label"]
    return [{k: (data[k][i] if k in ("c1", "c2") else float(data[k][i]))
             for k in names} for i in range(lo, hi)]


def auroc(scores: np.ndarray, y: np.ndarray) -> float:
    """Rank-sum AuROC with tie-averaged ranks, in numpy — independent of
    the repository's on-device metric kernels."""
    _, inv, counts = np.unique(scores, return_inverse=True,
                               return_counts=True)
    upper = np.cumsum(counts)
    ranks = (upper - (counts - 1) / 2.0)[inv]
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


# ---------------------------------------------------------------------------
# Fault accounting
# ---------------------------------------------------------------------------

def fault_counts() -> Dict[str, float]:
    """Process-wide ``tg_faults_total`` by kind (metrics are switched on for
    the whole run): catches recoveries recorded on threads that own no
    FaultLog, e.g. a plan fallback on the serving batcher thread."""
    from transmogrifai_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.registry().snapshot().get("tg_faults_total", {})
    return {k.split("=", 1)[-1]: float(v) for k, v in snap.items()}


def check_no_faults(where: str, log=None) -> None:
    """Fail on any recovery: the named FaultLog (when given) and the
    process-wide fault counter must both be clean."""
    if log is not None:
        bad = [r.to_json() for r in log.reports if r.kind in _FAULT_KINDS]
        check(not bad, f"{where}: fault log not clean: {bad[:3]}")
    counts = {k: v for k, v in fault_counts().items()
              if k in _FAULT_KINDS and v}
    check(not counts, f"{where}: tg_faults_total not clean: {counts}")


# ---------------------------------------------------------------------------
# Phase: native libraries, rebuilt from the tracked sources
# ---------------------------------------------------------------------------

def phase_native() -> Dict[str, bool]:
    import transmogrifai_tpu
    from transmogrifai_tpu.utils import streaming_histogram, text_native

    build = os.path.join(os.path.dirname(transmogrifai_tpu.__file__),
                         "native", "_build")
    shutil.rmtree(build, ignore_errors=True)
    return {"textops": text_native.native_available(),
            "streaminghist": streaming_histogram.native_available()}


# ---------------------------------------------------------------------------
# Phase: kernels against their XLA twins
# ---------------------------------------------------------------------------

def _compiled(fn, *args):
    """jit-compile ``fn`` at ``args``; on the chip the lowered program must
    hold a Mosaic custom call (a kernel that gave way to its XLA twin, or
    ran interpreted, is not what this phase is for)."""
    import jax
    from transmogrifai_tpu.histeng.kernels import _interpret
    lowered = jax.jit(fn).lower(*args)
    if not _interpret():
        check("tpu_custom_call" in lowered.as_text(),
              "kernel dispatch produced no Mosaic custom call")
    return lowered.compile()


def _twin(fn, *args):
    import jax
    return jax.jit(fn)(*args)


def _close(name: str, got, want, rtol: float, atol_rel: float) -> float:
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != "
          f"{want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
    scale = float(np.abs(want).max()) or 1.0
    err = float(np.abs(got - want).max()) / scale
    check(bool(np.allclose(got, want, rtol=rtol, atol=atol_rel * scale)),
          f"{name}: differs from its XLA twin (max err / max|want| = "
          f"{err:.3g}, rtol {rtol}, atol {atol_rel}*max)")
    return err


def _row_blocks(n: int, blk: int):
    return [(lo, min(lo + blk, n)) for lo in range(0, n, blk)]


def _heap_tables(rng, T, depth, d, nb):
    H = 2 ** depth - 1
    feat = rng.randint(0, d, (T, H)).astype(np.int32)
    bins = rng.randint(0, nb, (T, H)).astype(np.int32)
    bins[rng.rand(T, H) < 0.2] = nb                    # stopped nodes
    return feat, bins


def _chain_tables(rng, T, depth, W, d, nb):
    """Random but consistent slot chains: base pointers stay inside the
    next level's width (as tests/test_deep_trees.py builds them)."""
    feat = rng.randint(0, d, (T, depth, W)).astype(np.int32)
    bins = rng.randint(0, nb - 1, (T, depth, W)).astype(np.int32)
    base = np.zeros((T, depth, W), np.int32)
    for lv in range(depth):
        Wl, Wn = min(2 ** lv, W), min(2 ** (lv + 1), W)
        base[:, lv, :Wl] = rng.randint(0, max(Wn - 1, 1), (T, Wl))
        stop = rng.rand(T, Wl) < 0.2
        bins[:, lv, :Wl] = np.where(stop, nb, bins[:, lv, :Wl])
    return feat, bins, base


def kernel_cases(seed: int, full: bool = True):
    """(name, thunk) per kernel check; each thunk runs one Pallas kernel
    through its dispatching entry point, compares it with its XLA twin and
    returns max|err| / max|want|. Tolerances are those of
    tests/test_tree_hist.py and tests/test_deep_trees.py, the absolute part
    scaled by max|want| (sums over 65 536 rows are larger than the tests'
    few hundred). ``full``: the largest shapes the dispatch admits;
    otherwise interpret-sized."""
    import jax.numpy as jnp
    from transmogrifai_tpu.histeng import kernels as K
    from transmogrifai_tpu.ops import forest as F

    rng = np.random.RandomState(seed)
    nb = 32
    cases = []

    # -- histogram contraction: widest admitted stat block, and a narrow
    #    unaligned one (blk_b = 8, 5 features)
    def hist_case(S, d, B, exact, rtol, atol):
        name = f"hist[{'exact' if exact else 'bf16'}]S{S}d{d}B{B}"

        def run():
            codes = jnp.asarray(rng.randint(0, nb, (S, d)).astype(np.int32))
            A = jnp.asarray(rng.randn(S, B).astype(np.float32))
            got = _compiled(
                lambda c, a: K.hist_matmul(c, a, nb, exact=exact),
                codes, A)(codes, A)
            want = _twin(lambda c, a: K._hist_xla(c, a, nb, exact),
                         codes, A)
            return _close(name, got, want, rtol, atol)
        return name, run

    for S, d, B in ([(65536, 64, K._HIST_PALLAS_MAX_B), (8192, 5, 3)]
                    if full else [(600, 5, 3)]):
        cases.append(hist_case(S, d, B, False, 2e-2, 2e-2))
        cases.append(hist_case(S, d, B, True, 1e-4, 1e-4))

    # -- heap descent kernels at the envelope ops/forest.py admits, and
    #    slot-chain kernels at depth 12 with the refit leaf budget; the
    #    chain's tree axis spans two kernel calls (chunked at _T_CHAIN)
    n, d, blk = (65536, 64, 8192) if full else (300, 6, 300)
    T, depth = ((F._MAX_TREES_PALLAS, F._MAX_DEPTH_PALLAS) if full
                else (5, 3))
    Tc, cdepth, W = ((F._T_CHAIN + 8, 12, F._MAX_SLOTS) if full
                     else (3, 6, 8))
    k_pred = 128 if full else 2
    shared: Dict[str, Any] = {}

    def inputs():
        if not shared:
            shared["codes"] = jnp.asarray(
                rng.randint(0, nb, (n, d)).astype(np.int32))
            shared["aug"] = jnp.asarray(rng.randn(n, 4).astype(np.float32))
            shared["heap"] = tuple(map(
                jnp.asarray, _heap_tables(rng, T, depth, d, nb)))
            shared["leaf"] = jnp.asarray(
                rng.randn(T, 2 ** depth, k_pred).astype(np.float32))
            shared["chain"] = tuple(map(
                jnp.asarray, _chain_tables(rng, Tc, cdepth, W, d, nb)))
            shared["cleaf"] = jnp.asarray(rng.randn(
                Tc, min(2 ** cdepth, W), k_pred).astype(np.float32))
        return shared

    def leaf_sums():
        i = inputs()
        codes, aug, (feat, bins) = i["codes"], i["aug"], i["heap"]
        check(F._pallas_ok(depth, T), "heap envelope refuses its maximum")
        got = _compiled(lambda c, a: F.forest_leaf_sums(
            c, feat, bins, a, depth=depth, n_bins=nb), codes, aug)(codes,
                                                                  aug)
        want = sum(np.asarray(_twin(
            lambda c, a: F._leaf_sums_xla(c, feat, bins, a, depth=depth,
                                          n_bins=nb),
            codes[lo:hi], aug[lo:hi]), dtype=np.float64)
            for lo, hi in _row_blocks(n, blk))
        return _close("leaf_sums", got, want, 1e-4, 1e-4)

    def predict():
        i = inputs()
        codes, leaf, (feat, bins) = i["codes"], i["leaf"], i["heap"]
        got = _compiled(lambda c: F.forest_predict(
            c, feat, bins, leaf, depth=depth, n_bins=nb), codes)(codes)
        want = np.concatenate([np.asarray(_twin(
            lambda c: F._predict_xla(c, feat, bins, leaf, depth=depth,
                                     n_bins=nb), codes[lo:hi]))
            for lo, hi in _row_blocks(n, blk)])
        return _close("predict", got, want, 1e-4, 1e-4)

    def leaf_sums_chain():
        i = inputs()
        codes, aug, (cf, cb, ca) = i["codes"], i["aug"], i["chain"]
        got = _compiled(lambda c, a: F.forest_leaf_sums_chain(
            c, cf, cb, ca, a, n_bins=nb), codes, aug)(codes, aug)
        want = _twin(lambda c, a: F._leaf_sums_chain_xla(
            c, cf, cb, ca, a, n_bins=nb), codes, aug)
        return _close("leaf_sums_chain", got, want, 1e-4, 1e-4)

    def predict_chain():
        i = inputs()
        codes, cleaf, (cf, cb, ca) = i["codes"], i["cleaf"], i["chain"]
        got = _compiled(lambda c: F.forest_predict_chain(
            c, cf, cb, ca, cleaf, n_bins=nb), codes)(codes)
        want = _twin(lambda c: F._predict_chain_xla(
            c, cf, cb, ca, cleaf, n_bins=nb), codes)
        return _close("predict_chain", got, want, 1e-5, 1e-5)

    def aot_round_trip():
        # jax.export of a program holding a Mosaic call, through the
        # program store's own serialise/deserialise pair
        import jax
        from transmogrifai_tpu.programstore import aot
        i = inputs()
        codes, leaf, (feat, bins) = i["codes"], i["leaf"], i["heap"]
        fn = jax.jit(lambda c: F.forest_predict(
            c, feat, bins, leaf, depth=depth, n_bins=nb))
        loaded = aot.load_callable(aot.export_bytes(fn, (codes,)))
        check(bool(np.array_equal(np.asarray(loaded(codes)),
                                  np.asarray(fn(codes)))),
              "aot_round_trip: deserialized program differs from its "
              "source")
        return 0.0

    cases += [(f"leaf_sums T{T}depth{depth}", leaf_sums),
              (f"predict T{T}depth{depth}k{k_pred}", predict),
              (f"leaf_sums_chain T{Tc}depth{cdepth}W{W}", leaf_sums_chain),
              (f"predict_chain T{Tc}depth{cdepth}W{W}k{k_pred}",
               predict_chain),
              ("aot_round_trip[predict]", aot_round_trip)]
    return cases


def phase_kernels(seed: int, full: bool = True) -> Dict[str, float]:
    from transmogrifai_tpu.histeng.kernels import _use_pallas
    check(_use_pallas(), "kernel dispatch is not on the Pallas path")
    return {name: run() for name, run in kernel_cases(seed, full)}


# ---------------------------------------------------------------------------
# Phases: train, score, serve
# ---------------------------------------------------------------------------

def build_workflow(table, mesh=None):
    """The documented full-train workflow over ``table``. Returns
    (workflow, prediction feature, checked vector feature, selector)."""
    import transmogrifai_tpu as tg
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.workflow import OpWorkflow

    label = FeatureBuilder.RealNN("label").extract_field().as_response()
    feats = [FeatureBuilder.Real(f"x{i}").extract_field().as_predictor()
             for i in range(12)]
    feats += [FeatureBuilder.PickList(c).extract_field().as_predictor()
              for c in ("c1", "c2")]
    checked = tg.transmogrify(feats).sanity_check(label)
    selector = BinaryClassificationModelSelector.with_cross_validation()
    pred = selector.set_input(label, checked).get_output()
    wf = OpWorkflow().set_input_table(table).set_result_features(pred)
    if mesh is not None:
        wf = wf.with_mesh(mesh)
    return wf, pred, checked, selector


def phase_train(table, mesh=None) -> Dict[str, Any]:
    """``OpWorkflow.train()``; fails on any recovery it recorded."""
    from transmogrifai_tpu.impl.selector.model_selector import SelectedModel

    wf, pred, checked, selector = build_workflow(table, mesh)
    model = wf.train()
    where = "train[mesh]" if mesh is not None else "train"
    faults_json = model.summary()["faults"]
    dirty = {k: faults_json[k] for k in _SUMMARY_FAULT_KEYS
             if faults_json[k]}
    check(not dirty, f"{where}: summary()['faults'] not clean: "
          f"{json.dumps(dirty, default=str)[:600]}")
    check_no_faults(where, model._fault_log)
    selected = next(s for s in model.stages if isinstance(s, SelectedModel))
    s = selected.summary
    check(not s.quarantined,
          f"{where}: BestEstimator.quarantined: {s.quarantined[:3]}")
    folds = selector.validator.num_folds
    return {
        "model": model, "pred": pred, "checked": checked,
        "selector": selector, "family": s.best_model_type,
        "hyper": dict(s.best_hyper), "metric": float(s.best_metric_value),
        "fits": folds * sum(len(r.grid) for r in s.validation_results),
        "results": {(r.family, json.dumps(g, sort_keys=True)): float(m)
                    for r in s.validation_results
                    for g, m in zip(r.grid, r.mean_metrics)},
    }


def _scores(scored, pred) -> np.ndarray:
    """The winner's continuous class-1 score: the probability where the
    family emits one, else the margin (LinearSVC)."""
    col = scored[pred.name]
    keys = list(col.metadata["keys"])
    key = ("probability_1" if "probability_1" in keys
           else "rawPrediction_1")
    return np.asarray(col.values)[:, keys.index(key)]


def phase_score(model, pred, train_table, holdout_table,
                parity_rows: int = PARITY_ROWS,
                auroc_floor: float = AUROC_FLOOR) -> Dict[str, Any]:
    """``model.score()`` over the full table (finite), planned == eager on a
    slice, AuROC on the held-out rows above the floor."""
    from transmogrifai_tpu import plan as plan_mod
    from transmogrifai_tpu.robustness.policy import FaultLog

    log = FaultLog()
    with log.activate():
        scored = model.score(table=train_table)
        vals = np.asarray(scored[pred.name].values)
        check(vals.shape[0] == train_table.num_rows,
              f"score: {vals.shape[0]} rows for {train_table.num_rows}")
        check(bool(np.all(np.isfinite(vals))), "score: non-finite output")

        held = model.score(table=holdout_table)
        auc = auroc(_scores(held, pred),
                    np.asarray(holdout_table["label"].values))
        check(auc > auroc_floor,
              f"score: held-out AuROC {auc:.4f} <= floor {auroc_floor}")

        part = holdout_table.take(np.arange(
            min(parity_rows, holdout_table.num_rows)))
        planned = np.asarray(model.score(table=part)[pred.name].values)
        plan_mod.enable_planning(False)
        try:
            eager = np.asarray(model.score(table=part)[pred.name].values)
        finally:
            plan_mod.enable_planning(None)
        # docs/plan.md: planned output is bit-identical to eager dispatch
        check(bool(np.array_equal(planned, eager)),
              "score: planned and eager outputs differ (max abs "
              f"{float(np.abs(planned - eager).max()):.3g})")
    check_no_faults("score", log)
    return {"auroc": auc}


def phase_serve(model, data, lo: int, hi: int) -> Dict[str, Any]:
    """``save()`` -> ``ModelRegistry.load()`` -> one ``submit()`` per row in
    ``data[lo:hi]``; every future resolves to ``model.score()``'s record
    for that row, nothing shed, degraded or compiled around a failure."""
    from transmogrifai_tpu.local.scoring import serve_record_builder
    from transmogrifai_tpu.programstore import store as pstore
    from transmogrifai_tpu.serving import ModelRegistry
    from transmogrifai_tpu.serving.breaker import CLOSED

    rows = rows_of(data, lo, hi)
    expected = serve_record_builder(model)(
        model.score(table=table_of(data, lo, hi)), len(rows))
    path = tempfile.mkdtemp(prefix="tg_smoke_model_")
    try:
        model.save(path)
        check(pstore.stats()["exportErrors"] == 0,
              "serve: AOT export failed at save()")
        with ModelRegistry() as reg:
            rt = reg.load("smoke", path)
            futs = [rt.submit(r) for r in rows]
            got = [f.result(timeout=300) for f in futs]
            summary = rt.summary()
            breaker = rt.breaker.state
            check_no_faults("serve", rt.fault_log)
    finally:
        shutil.rmtree(path, ignore_errors=True)

    warm = summary["warm"]
    check(bool(warm and warm["ok"]), f"serve: warm-up failed: {warm}")
    check(warm["aotHits"] > 0,
          f"serve: load() deserialized no stored program: {warm}")
    check(breaker == CLOSED, f"serve: breaker is {breaker}")
    check(summary["rowsScored"] == len(rows),
          f"serve: {summary['rowsScored']} rows scored of {len(rows)}")
    for key in ("degradedRows", "quarantinedRows"):
        check(not summary[key], f"serve: {key} = {summary[key]}")
    check(not any(summary["shed"].values()),
          f"serve: requests shed: {summary['shed']}")

    # one padded program per bucket, every stage a per-row map: a served
    # record is bit-equal to the batch score of the same row
    # (tests/test_serving.py holds the CPU to the same)
    wrong = [i for i, (g, e) in enumerate(zip(got, expected)) if g != e]
    check(not wrong, f"serve: {len(wrong)} record(s) differ from "
          f"model.score(), first row {wrong[:1]}: "
          f"{got[wrong[0]] if wrong else None} vs "
          f"{expected[wrong[0]] if wrong else None}")
    return {"requests": len(rows), "aotHits": warm["aotHits"],
            "warmCompiles": warm["compiles"]}


# ---------------------------------------------------------------------------
# Phase: four chips
# ---------------------------------------------------------------------------

def _device_memory(devices) -> List[Dict[str, int]]:
    """``memory_stats()`` of each device; a device that reports none fails
    the run (the check below could not see it)."""
    stats = [dev.memory_stats() for dev in devices]
    silent = [str(d) for d, st in zip(devices, stats) if st is None]
    check(not silent, f"mesh: no memory_stats() from {silent}")
    return stats


def phase_mesh(table, holdout_table, single: Dict[str, Any],
               single_auroc: float) -> Dict[str, Any]:
    """Item 1's train and score under a ``data=4`` mesh, same process: no
    cost-model downgrade, every device holds shards of the sweep's inputs,
    and winner + held-out AuROC agree with the single-chip phase."""
    import jax
    from transmogrifai_tpu.observability import metrics as obs_metrics
    from transmogrifai_tpu.parallel import MeshSpec, make_mesh

    devices = jax.devices()[:4]
    mesh = make_mesh(MeshSpec(data=4, model=1), devices=devices)
    before = _device_memory(devices)
    out = phase_train(table, mesh=mesh)
    validator = out["selector"].validator
    sharding, shards = validator.last_sweep_sharding, validator.last_sweep_shards
    held = out["model"].score(table=holdout_table)
    check(bool(np.all(np.isfinite(np.asarray(
        held[out["pred"].name].values)))), "mesh: non-finite score")
    auc = auroc(_scores(held, out["pred"]),
                np.asarray(holdout_table["label"].values))
    after = _device_memory(devices)
    downgrades = sum(obs_metrics.registry().snapshot().get(
        "tg_mesh_downgrade_total", {}).values())
    # peak_bytes_in_use is a process-lifetime peak: the first device's
    # includes the single-chip phases, the others were idle until now
    peaks = [(int(b["peak_bytes_in_use"]), int(a["peak_bytes_in_use"]))
             for b, a in zip(before, after)]
    print(f"mesh observed: winner {out['family']} {out['hyper']} "
          f"(CV {out['metric']:.6f}), held-out AuROC {auc:.10f}, "
          f"downgrades {downgrades}, sweep sharding {sharding}, shards "
          f"{shards}, peak bytes (before, after) {peaks}", flush=True)

    check(downgrades == 0, f"mesh: {downgrades} sweep downgrade(s)")
    check(sharding is not None and set(sharding.device_set) == set(devices),
          f"mesh: sweep inputs were placed as {sharding}")
    # one shard of the sweep's table per device, all of one shape, together
    # at least the rows the splitter leaves for training
    shapes = {shape for _, shape in shards}
    check(sorted(d.id for d, _ in shards) == sorted(d.id for d in devices)
          and len(shapes) == 1,
          f"mesh: sweep table shards are {shards}")
    (shard_rows, width), = shapes
    check(4 * shard_rows >= table.num_rows // 2,
          f"mesh: shards of {shard_rows} rows for {table.num_rows}")
    shard_bytes = shard_rows * width * 4
    for dev, (_, peak) in zip(devices, peaks):
        check(peak >= shard_bytes,
              f"mesh: {dev} peaked at {peak} bytes, below one input shard "
              f"({shard_bytes})")
    check(abs(auc - single_auroc) <= MESH_AUROC_TOL,
          f"mesh: held-out AuROC {auc:.4f} vs single-chip "
          f"{single_auroc:.4f} (tolerance {MESH_AUROC_TOL})")
    check(out["family"] == single["family"],
          f"mesh: winner {out['family']} vs single-chip {single['family']}")
    if out["hyper"] != single["hyper"]:
        # a different grid point may win only as a near-tie in the
        # single-chip phase's own CV metrics
        key = (out["family"], json.dumps(out["hyper"], sort_keys=True))
        gap = abs(single["results"][key] - single["metric"])
        check(gap <= MESH_TIE_TOL,
              f"mesh: winner {out['hyper']} vs single-chip "
              f"{single['hyper']} (CV metric gap {gap:.3g})")
    return {"family": out["family"], "hyper": out["hyper"], "auroc": auc,
            "fits": out["fits"], "shards": [shape for _, shape in shards],
            "peakBytes": [peak for _, peak in peaks]}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from transmogrifai_tpu.histeng.kernels import _interpret
    from transmogrifai_tpu.observability import metrics as obs_metrics
    from transmogrifai_tpu.utils.jax_cache import (cache_stats,
                                                   ensure_compilation_cache)

    ensure_compilation_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no accelerator (jax backend is "
              f"{jax.default_backend()!r}); this script runs on a TPU only",
              file=sys.stderr)
        return 2
    obs_metrics.enable_metrics(True)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}  jax {jax.__version__}  jaxlib "
          f"{jaxlib.__version__}  compile cache: "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    check(not _interpret(), "Pallas kernels would run interpreted")

    walls: Dict[str, float] = {}
    report: Dict[str, Any] = {}

    def timed(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        walls[name] = round(time.perf_counter() - t0, 2)
        print(f"[smoke timing] {name}: {walls[name]} s", flush=True)
        return out

    report["native"] = timed("native", phase_native)
    print(f"native libraries in use: {report['native']}", flush=True)
    report["kernelErr"] = timed("kernels", phase_kernels, args.seed)

    n = ROWS
    data = timed("data", make_data, n + HOLDOUT_ROWS, args.seed)
    table = table_of(data, 0, n)
    holdout = table_of(data, n, n + HOLDOUT_ROWS)

    single = timed("train", phase_train, table)
    check(single["fits"] == DEFAULT_GRID_FITS,
          f"train: {single['fits']} fits, the stock default grids make "
          f"{DEFAULT_GRID_FITS} (is TG_FAST_GRIDS set?)")
    report["train"] = {k: single[k] for k in
                       ("family", "hyper", "metric", "fits")}
    report["score"] = timed("score", phase_score, single["model"],
                            single["pred"], table, holdout)
    report["serve"] = timed("serve", phase_serve, single["model"], data,
                            n, n + SERVE_REQUESTS)
    if len(jax.devices()) >= 4:
        report["mesh"] = timed("mesh", phase_mesh, table, holdout, single,
                               report["score"]["auroc"])
    check_no_faults("end of run")

    print(json.dumps({"smokeTimingsSecs": walls, **report, "rows": n,
                      "seed": args.seed, "compileCache": cache_stats(),
                      "claim": None}, default=str), flush=True)
    # the driver reads the last line: exactly these keys
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
