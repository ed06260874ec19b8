"""Validators: cross-validation and train/validation split over vmapped grids.

The TPU re-design of the reference's thread-pool validator
(reference: core/.../impl/tuning/OpValidator.scala:270-322 — one Scala Future
per model × fold, pool of 8; OpCrossValidation.scala:139-181 kFold;
OpTrainValidationSplit.scala:40-80): here folds become static 0/1 row-mask
vectors, and the whole |folds| × |grid| sweep for a model family is ONE
``fit_batch`` call — a single jitted, vmapped XLA program whose inner matmuls
tile onto the MXU. Parallelism is not 8 threads; it is the full batch dimension
on device, shardable across chips over the 'model' mesh axis.
"""
from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...manifest import sentinel_phase
from ...models.api import FittedParams, ModelFamily
from ...observability import blackbox as _blackbox
from ...observability import devicemem as _devicemem
from ...observability import ledger as _obs_ledger
from ...observability import metrics as _obs_metrics
from ...observability import trace as _obs_trace
from ...observability.trace import span as _obs_span, tracing_enabled
from ...ops import metrics as _metrics
from ...ops.metrics import (
    aupr_masked, auroc_masked, binary_threshold_metrics_masked,
    log_loss_masked, multiclass_metrics_masked, regression_metrics_masked,
)
from ...parallel.distributed import (
    _count_transfer_bytes, fetch_to_host, retrying_device_put,
)
from ...parallel.mesh import mesh_span_attrs, sweep_mesh_decision
from ...robustness import faults
from ...robustness.guards import (
    AllCandidatesFailedError, quarantine_non_finite,
)
from ...utils.jax_cache import cache_stats
from ...utils.padding import bucket_for
from .sweep_checkpoint import candidate_key, sweep_fingerprint

logger = logging.getLogger(__name__)


@dataclass
class ValidationResult:
    """Per-(family, grid-point) averaged validation metric
    (reference ModelSelectorSummary validation results)."""
    family: str
    grid: List[Dict[str, Any]]
    metric_name: str
    fold_metrics: np.ndarray        # (F, G)
    mean_metrics: np.ndarray        # (G,)

    def to_json(self):
        return {
            "modelType": self.family,
            "metricName": self.metric_name,
            "grid": self.grid,
            "foldMetrics": self.fold_metrics.tolist(),
            "meanMetrics": self.mean_metrics.tolist(),
        }


@dataclass
class BestEstimator:
    """Winner of validation (reference OpValidator.wrapBestEstimator :147).
    ``quarantined`` carries the records of candidates excluded from
    selection (non-finite metrics or a fit that threw) — they surface in
    ``ModelSelectorSummary`` with their failure reasons."""
    family_name: str
    hyper: Dict[str, Any]
    metric_value: float
    results: List[ValidationResult] = field(default_factory=list)
    quarantined: List[Dict[str, Any]] = field(default_factory=list)


@dataclass
class PendingValidation:
    """A queued-but-unsynced validate(): every family's device programs are
    dispatched; ``resolve()`` materializes the metrics and picks the winner.
    Lets workflow-level CV queue ALL folds' programs back-to-back before a
    single host sync (the reference's analog: concurrent fold Futures,
    OpValidator.applyDAG :228-256)."""
    _finish: Any

    def resolve(self) -> BestEstimator:
        return self._finish()


@functools.lru_cache(maxsize=None)
def _metric_fn(problem: str, metric: str, batched_y: bool = False,
               binned: "Optional[bool]" = None):
    """Jitted batched metric over (B, n) scores with (B, n) val masks,
    honoring the evaluator's requested metric name (reference: the validator
    optimizes whatever evaluator the selector was configured with).
    ``batched_y``: labels are (B, n) per-config (the fold-sliced scoring
    path, where each config's rows are its own fold's validation rows)
    instead of one shared (n,) vector."""
    y_ax = 0 if batched_y else None
    if problem == "binary":
        if metric in ("AuPR", "AuROC"):
            base = {"AuPR": aupr_masked, "AuROC": auroc_masked}[metric]
            if binned is not None:
                from functools import partial as _partial
                base = _partial(base, binned=binned)
            return jax.jit(jax.vmap(base, in_axes=(0, y_ax, 0)))
        if metric in ("Precision", "Recall", "F1", "Error"):
            def one_b(scores, y, mask):
                return binary_threshold_metrics_masked(scores, y, mask)[metric]
            return jax.jit(jax.vmap(one_b, in_axes=(0, y_ax, 0)))
        if metric == "LogLoss":
            return jax.jit(jax.vmap(log_loss_masked, in_axes=(0, y_ax, 0)))
        raise ValueError(f"unknown binary validation metric '{metric}'")
    if problem == "multiclass":
        if metric not in ("F1", "Precision", "Recall", "Error"):
            raise ValueError(f"unknown multiclass validation metric '{metric}'")

        def one(probs, y, mask, num_classes):
            pred = probs.argmax(axis=-1).astype(jnp.int32)
            return multiclass_metrics_masked(
                pred, y.astype(jnp.int32), mask, num_classes)[metric]
        return jax.jit(jax.vmap(one, in_axes=(0, y_ax, 0, None)),
                       static_argnums=(3,))
    if problem == "regression":
        if metric not in ("RootMeanSquaredError", "MeanSquaredError",
                          "MeanAbsoluteError", "R2"):
            raise ValueError(f"unknown regression validation metric '{metric}'")

        def one_r(pred, y, mask):
            return regression_metrics_masked(pred, y, mask)[metric]
        return jax.jit(jax.vmap(one_r, in_axes=(0, y_ax, 0)))
    raise ValueError(problem)


#: fused per-family sweep programs, keyed by (family, grid, fold/metric
#: config) — reused across validate() calls so repeated workflow fits
#: pay one compile. LRU-bounded: each entry pins a jitted
#: executable plus its tiled host grid constants, so a long-lived process
#: fitting many distinct grids would otherwise grow compiled-program memory
#: without bound (eviction just re-pays the pre-existing compile cost)
_FUSED_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_FUSED_CACHE_MAX = int(os.environ.get("TG_FUSED_CACHE_MAX", "32"))


def _arg_nbytes(a) -> int:
    """Device bytes of one dispatch argument (shape × itemsize)."""
    try:
        itemsize = int(np.dtype(getattr(a, "dtype", np.float32)).itemsize)
    except TypeError:
        itemsize = 4
    return int(np.prod(np.shape(a))) * itemsize


def _fused_cache_get(key):
    prog = _FUSED_CACHE.get(key)
    if prog is not None:
        _FUSED_CACHE.move_to_end(key)
    return prog


def _fused_cache_put(key, prog) -> None:
    _FUSED_CACHE[key] = prog
    _FUSED_CACHE.move_to_end(key)
    while len(_FUSED_CACHE) > _FUSED_CACHE_MAX:
        evicted_key, _ = _FUSED_CACHE.popitem(last=False)
        # the compile ledger classifies the eventual rebuild of this key
        # as cache-eviction instead of an unexplained cold build
        _obs_ledger.record_eviction(_obs_ledger.cache_key_hash(evicted_key))


def _hist_mesh_ctx(family, mesh):
    """Histogram-engine mesh context for a family's program trace/export:
    tree families (``uses_hist_engine``) pin their K-blocked contraction's
    row blocks to the 'data' axis; everything else is a no-op context."""
    from ...histeng import engine_mesh
    return engine_mesh(
        mesh if getattr(family, "uses_hist_engine", False) else None)


def clear_mesh_programs() -> None:
    """Drop mesh-keyed fused programs. Each pins a ``jax.sharding.Mesh``
    plus per-device executable buffers; the test harness asserts none leak
    across tests (a stale program keyed to a dead 8-device test mesh would
    silently hold every device's buffers alive for the whole session)."""
    for k in mesh_program_keys():
        _FUSED_CACHE.pop(k, None)


def mesh_program_keys():
    """Cache keys of mesh-compiled fused programs (no-leak fixture probe)."""
    return [k for k in _FUSED_CACHE
            if any(isinstance(e, Mesh) for e in k)]


def _make_fused_program(family, garr_np, G: int, F: int, problem: str,
                        metric_name: str, num_classes: int, exact: bool,
                        sliced: bool, binned, mesh=None, x_ndim: int = 2):
    """ONE jitted program for a family's whole sweep branch: build the fold
    weights from the per-row fold ids, fit all F·G configs, score each
    fold's validation partition, and reduce to the padded metric vector.

    Fusing the branch removes the per-executable dispatch bubbles of the
    eager glue (~900 small executables per default sweep, each a separate
    launch) and lets XLA dead-code-eliminate every fitted parameter the
    sweep never reads (only the metric vector leaves the program; e.g. tree
    raw-threshold tables exist solely for the refit path). The grid arrays
    are host constants, so the tree families' per-depth bucketing stays
    static under the trace.

    ``mesh``: compile the same branch as one GSPMD program with explicit
    ``NamedSharding`` in/out specs — rows over 'data', the (F·G) config
    batch over 'model' (families with ``shardable=False`` keep their
    configs whole and only shard rows). The fold train-weights are built
    INSIDE the trace from the uint8 fold-id vector, so no (F, n) tensor is
    ever assembled on the host or device_put per family. The metric stage
    is re-sharded config-parallel/row-replicated (``P('model', None)``):
    the sort/cumulative-scan chain of AuROC/AuPR is partitioner-hostile
    along the row axis (XLA's SPMD pass miscompiles the composed
    scan+concat sequence when rows are sharded — see docs/parallel.md),
    and per-config metrics over replicated rows are both correct and the
    natural parallel axis. Families with ``traced_grid_ok`` take their
    tiled grid as ONE packed (keys, F·G) f32 device argument, sharded over
    'model' and DONATED — XLA may alias the block for per-family scratch
    instead of re-allocating; tree families keep host-constant grids (their
    per-depth bucketing must stay static under the trace). Returns
    ``(prog, grid_keys)`` where ``grid_keys`` is None for constant-grid
    families and the packed-block key order otherwise.
    """
    B_true = F * G
    B_m = -(-B_true // 32) * 32
    metric = _metric_fn(problem, metric_name, batched_y=sliced, binned=binned)
    tiled = {k: np.tile(v, F) for k, v in garr_np.items()}
    shardable = getattr(family, "shardable", True) if mesh is not None \
        else True
    traced_grid = (mesh is not None and shardable
                   and getattr(family, "traced_grid_ok", False))
    grid_keys = tuple(sorted(tiled)) if traced_grid else None

    def prog(*args):
        # a trace-time name only: the module stays ``jit_prog``, the ops of
        # this family's branch carry ``sweep.<family>`` in their op names
        with jax.named_scope(f"sweep.{family.name}"):
            return branch(*args)

    def branch(X, y, ids_d, *rest):
        # call convention: [Xf, yf, fvalid] when sliced, then [gblock]
        # when the family takes its grid as a traced (donated) argument
        Xf = yf = fvalid = gblock = None
        if sliced:
            Xf, yf, fvalid = rest[0], rest[1], rest[2]
            rest = rest[3:]
        if traced_grid:
            gblock = rest[0]
        f_iota = jnp.arange(F, dtype=jnp.uint8)[:, None]
        train_w = ((ids_d[None, :] != f_iota)
                   & (ids_d[None, :] != jnp.uint8(F + 1))
                   ).astype(jnp.float32)                    # (F, n)
        W = jnp.repeat(train_w, G, axis=0)                  # (F*G, n)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            W = jax.lax.with_sharding_constraint(
                W, NamedSharding(mesh, P("model" if shardable else None,
                                         "data")))
        # gblock's config axis is zero-padded up to the 'model'-shard
        # multiple (device_put demands divisibility); slice before the fit
        g = ({k: gblock[i][:B_true] for i, k in enumerate(grid_keys)}
             if traced_grid else tiled)
        params = (family.fit_batch(X, y, W, g, num_classes) if exact
                  else family.sweep_fit_batch(X, y, W, g, num_classes))
        if sliced:
            per_fold = [
                family.predict_batch(
                    family.slice_params(params, f * G, (f + 1) * G),
                    Xf[f], num_classes)
                for f in range(F)
            ]
            scores = jnp.concatenate(per_fold, axis=0)      # (F*G, nf[, C])
            Y = jnp.repeat(yf, G, axis=0)
            VM = jnp.repeat(fvalid, G, axis=0)
        else:
            scores = family.predict_batch(params, X, num_classes)
            Y = y
            VM = jnp.repeat(ids_d[None, :] == f_iota, G, axis=0)
        if B_m != B_true:
            scores = jnp.pad(scores, ((0, B_m - B_true),)
                             + ((0, 0),) * (scores.ndim - 1))
            VM = jnp.pad(VM, ((0, B_m - B_true), (0, 0)))
            if sliced:
                Y = jnp.pad(Y, ((0, B_m - B_true), (0, 0)))
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            cfg_sh = NamedSharding(
                mesh, P("model", *([None] * (scores.ndim - 1))))
            row_sh = NamedSharding(mesh, P("model", None))
            scores = jax.lax.with_sharding_constraint(scores, cfg_sh)
            VM = jax.lax.with_sharding_constraint(VM, row_sh)
            # Y: (B, nf) per-config labels when sliced, the shared (n,)
            # vector otherwise — either way the metric stage needs its row
            # axis REPLICATED (see the partitioner note above)
            Y = jax.lax.with_sharding_constraint(
                Y, row_sh if sliced else NamedSharding(mesh, P(None)))
        if problem == "multiclass":
            return metric(scores, Y, VM, num_classes)
        return metric(scores, Y, VM)

    if mesh is None:
        return jax.jit(prog), grid_keys
    from jax.sharding import NamedSharding, PartitionSpec as P
    row = lambda nd: NamedSharding(mesh, P("data", *([None] * (nd - 1))))
    in_sh = [row(x_ndim), row(1), row(1)]
    if sliced:
        # Xf feeds the row-parallel per-fold predicts → rows over 'data';
        # yf / fvalid are consumed ONLY by the config-parallel metric
        # stage, which needs rows replicated — uploading them sharded just
        # buys an all-gather (and an XLA "involuntary rematerialization"
        # warning) inside every family's program
        in_sh += [NamedSharding(mesh, P(None, "data",
                                        *([None] * (x_ndim - 1)))),
                  NamedSharding(mesh, P(None)),
                  NamedSharding(mesh, P(None))]
    donate = ()
    if traced_grid:
        in_sh.append(NamedSharding(mesh, P(None, "model")))
        donate = (len(in_sh) - 1,)
    return jax.jit(prog, in_shardings=tuple(in_sh),
                   out_shardings=NamedSharding(mesh, P(None)),
                   donate_argnums=donate), grid_keys


def _put(x, spec, mesh, site: str):
    """Place ``x`` under ``mesh`` partitioned as ``spec``, retrying a
    transient transfer failure; ``site`` names the upload in the fault
    log."""
    return retrying_device_put(x, NamedSharding(mesh, spec), site=site)


@dataclass(frozen=True, eq=False)
class _SweepInputs:
    """What every family's program of one sweep reads, placed once and
    shared (under a mesh: with its sharding), and what each is built for:
    made by ``_place_inputs``."""
    X: Any                  # (n_pad, ...) rows, zeros past n: the bucket
    y: Any                  # (n_pad,)
    ids_d: Any              # (n_pad,) uint8 fold of each row: F trains only,
    #                         F + 1 is padding (never trains, never validates)
    n: int
    n_pad: int
    F: int
    vm_np: np.ndarray       # (F, n) host validation masks
    mesh: Any               # the mesh the cost model left engaged, or None
    max_eval_rows: Optional[int]
    fold_rows: int          # rows of a fold's gathered validation partition
    problem: str
    metric_name: str
    num_classes: int
    exact: bool             # fit_batch in place of sweep_fit_batch
    fold_sliced: bool       # score on fold_data, where the family can

    # a cached_property writes the instance's __dict__ directly, which a
    # frozen dataclass allows: built once, by the first family that uses it
    @functools.cached_property
    def fold_data(self):
        """``(Xf, yf, fvalid)``: each fold's validation rows gathered to
        (F, nf_b, ...). Fold-sliced scoring: every (fold, config) pair only
        needs ITS fold's validation rows, so predict + metric run on the
        gathered partitions (~n/F rows each, capped at ``max_eval_rows``)
        instead of all n rows and a mask — an F x cut on the heavy tree
        predicts, and with the cap the gather beats full-row masked scoring
        even for single-matmul predicts."""
        F, cap, X, nf_b = self.F, self.max_eval_rows, self.X, self.fold_rows
        fidx = np.zeros((F, nf_b), np.int32)
        fvalid = np.zeros((F, nf_b), bool)
        for f in range(F):
            rows = np.nonzero(self.vm_np[f])[0]
            if cap is not None and len(rows) > cap:
                # deterministic strided subsample: validation METRIC
                # estimates use <= cap rows per fold (std of AuROC at
                # 65k rows ~2e-3 — far below fold-to-fold variance);
                # the winner's holdout/train evaluations and refit
                # always use full data
                rows = rows[np.linspace(0, len(rows) - 1, cap)
                            .astype(np.int64)]
            fidx[f, :len(rows)] = rows
            fvalid[f, :len(rows)] = True
        fidx_d = jnp.asarray(fidx.reshape(-1))
        _count_transfer_bytes(fidx_d, "h2d")
        Xf = X[fidx_d].reshape((F, nf_b) + X.shape[1:])
        yf = self.y[fidx_d].reshape(F, nf_b)
        fvalid_d = jnp.asarray(fvalid)
        _count_transfer_bytes(fvalid_d, "h2d")
        if self.mesh is not None:
            # Xf rows shard over 'data' (feeds the row-parallel per-fold
            # predicts); yf / fvalid replicate — they are only read by the
            # config-parallel metric stage (round-3 forced full-row masked
            # scoring under a mesh, silently dropping the eval-row cap — the
            # mesh sweep then did MORE per-chip predict work than one chip)
            site = "sweep.fold_upload"
            Xf = _put(Xf, P(None, "data", *([None] * (X.ndim - 1))),
                      self.mesh, site)
            yf = _put(yf, P(None), self.mesh, site)
            fvalid_d = _put(fvalid_d, P(None), self.mesh, site)
        return Xf, yf, fvalid_d


def _engage_mesh(mesh, X, y, n: int, n_configs: int):
    """``(mesh, X, y)`` this sweep runs under: ``mesh`` as asked for, or
    None where there is none or the cost model (docs/parallel.md) turns it
    down. Engaging a mesh costs collectives + cross-device layout on EVERY
    fit/predict/metric of the sweep; when the per-chip slice is too small
    to amortize that, the sweep is downgraded to the single-device fused
    path — bit-identical to running with no mesh at all (same programs,
    same buckets). The decision is observable: tg_mesh_downgrade_total + a
    sweep.mesh_downgrade span event carrying the measured sizes."""
    if mesh is None:
        return None, X, y
    engage, detail = sweep_mesh_decision(mesh, n, n_configs)
    if engage:
        return mesh, X, y
    _obs_metrics.inc_counter(
        "tg_mesh_downgrade_total", 1.0,
        help="sweeps downgraded to the single-device fused path "
             "by the mesh cost model")
    _obs_trace.add_event("sweep.mesh_downgrade", **detail)
    logger.info("mesh sweep downgraded to single-device: %s", detail)
    # "single-device" must hold for the inputs too: rows an upstream mesh
    # stage left sharded would turn the fused one-device program into a
    # GSPMD program, and a tree family's Mosaic kernels (traced with no
    # engine mesh) cannot be partitioned — the compiler refuses them
    X, y = jax.device_put((X, y), mesh.devices.flat[0])
    return None, X, y


def _place_inputs(X, y, vm_np: np.ndarray, mesh, padded_rows: Optional[int],
                  max_eval_rows: Optional[int], **built_for) -> _SweepInputs:
    """Pad the table to its row bucket, encode the (F, n) validation masks
    as one fold id a row, and place all three under ``mesh`` (None: where
    they are). ``built_for``: the fields of ``_SweepInputs`` that say what
    the programs are built for."""
    F, n = vm_np.shape
    # bucket the row count so every fit/predict/metric program is reused
    # across datasets/folds/stages (utils/padding.py); under a mesh the
    # bucket also aligns to the data axis for equal shards. Pad rows
    # carry zero weight and False val masks — results are unchanged.
    n_data = mesh.shape["data"] if mesh is not None else 1
    n_pad = bucket_for(n, multiple_of=n_data)
    if padded_rows is not None:
        # the caller gathered X straight into its row bucket (zero rows
        # past len(y)): no second copy here
        if int(X.shape[0]) != int(padded_rows) or padded_rows < n \
                or padded_rows % n_data:
            raise ValueError(
                f"padded_rows={padded_rows}: X has {X.shape[0]} rows, "
                f"y {n}, the data axis {n_data}")
        n_pad = int(padded_rows)
    elif n_pad != n and mesh is not None:
        from ...parallel.sharded import pad_rows_sharded
        X = pad_rows_sharded(X, n_pad, mesh)
    elif n_pad != n:
        X = jnp.pad(X, ((0, n_pad - n),) + ((0, 0),) * (X.ndim - 1))
    if n_pad != n:
        y = jnp.pad(y, (0, n_pad - n))
    # ship ONE byte per row and expand masks on device: each row sits in
    # at most one validation fold (TVS leaves train-only rows at id=F),
    # so the (F, n) float/bool masks never cross the host<->device link
    # (n bytes vs 5Fn)
    if F > 1 and int(vm_np.sum(axis=0).max()) > 1:
        raise ValueError(
            "validation masks must be disjoint (each row in at most one "
            "fold); overlapping masks would silently leak validation "
            "rows into other folds' training sets under the fold-id "
            "encoding")
    fold_ids = np.where(vm_np.any(axis=0), vm_np.argmax(axis=0),
                        F).astype(np.uint8)
    ids_d = jnp.asarray(fold_ids)
    _count_transfer_bytes(ids_d, "h2d")
    if n_pad != n:  # sentinel F+1: never trains, never validates
        ids_d = jnp.pad(ids_d, (0, n_pad - n), constant_values=F + 1)
    if mesh is not None:
        # the per-family device_put of (F·G, n) weight tensors is gone:
        # fold masks are built inside each trace from the uint8 id vector
        site = "sweep.table_upload"
        X = _put(X, P("data", *([None] * (X.ndim - 1))), mesh, site)
        y = _put(y, P("data"), mesh, site)
        ids_d = _put(ids_d, P("data"), mesh, site)
    # the largest fold's validation rows, capped, in their own bucket
    nf = int(vm_np.sum(axis=1).max()) if F > 0 else 0
    if max_eval_rows is not None and nf > max_eval_rows:
        nf = max_eval_rows
    return _SweepInputs(X, y, ids_d, n, n_pad, F, vm_np, mesh, max_eval_rows,
                        bucket_for(max(nf, 1), multiple_of=n_data),
                        **built_for)


def _program_for(family, grid, inputs: _SweepInputs, sliced: bool):
    """WHICH program runs this family's branch → ``(prog, grid_keys,
    store_as, garr_np)``. With or without a mesh the branch is ONE fused
    jitted program (see _make_fused_program); ``doc`` below is the one
    description of what it depends on, and the fused cache's key, the
    compile ledger's record and the program store's fingerprint are all made
    from it. ``store_as`` is None unless this call BUILT a program the store
    can hold: then it is how ``offer_segment`` names it; ``garr_np`` is the
    grid as host arrays where a build asked for them."""
    X, mesh, F, G = inputs.X, inputs.mesh, inputs.F, len(grid)
    # pin binned-vs-exact AuROC/AuPR to the PRE-slice row count so
    # fold-sliced and full-row scoring choose the same algorithm (the
    # threshold is read as the metrics read it: at this call)
    binned = (inputs.n_pad >= _metrics._BINNED_MIN_N) if sliced else None
    grid_repr = repr([sorted(g.items()) for g in grid])
    # every traced dimension but the row bucket, which the ledger and the
    # store take beside the fingerprint: a near-miss rebuild names exactly
    # which one changed (docs/observability.md "Compile & memory ledger")
    bucket = inputs.n_pad
    doc = {
        "F": int(F), "G": int(G), "problem": inputs.problem,
        "metric": inputs.metric_name,
        "numClasses": int(inputs.num_classes), "exact": bool(inputs.exact),
        "sliced": bool(sliced), "binned": binned,
        "foldRows": inputs.fold_rows if sliced else None,
        "xShape": tuple(int(d) for d in X.shape[1:]),
        "mesh": mesh is not None,
        "grid": hashlib.sha256(grid_repr.encode()).hexdigest()[:12],
    }
    # flat, with the live Mesh an element (mesh_program_keys finds it so)
    key = (family, grid_repr, mesh, bucket, *doc.values())
    entry = _fused_cache_get(key)
    if entry is not None:
        return (*entry, None, None)
    key_hash = _obs_ledger.cache_key_hash(key)
    identity = f"sweep/{family.name}" + ("/mesh" if mesh is not None else "")
    # Storability mirrors _make_fused_program's grid logic: families that
    # take a traced DONATED grid block under a mesh (shardable +
    # traced_grid_ok) are not exportable; everything else — all
    # single-device programs, and mesh programs with host-constant grids
    # (the tree families, shardable=False) — is a pure function of family
    # × doc × row bucket. Mesh fingerprints additionally pin the axis sizes
    # and device count: an export from a different topology must never be
    # a hit.
    store_as = None
    if mesh is None or not (getattr(family, "shardable", True)
                            and getattr(family, "traced_grid_ok", False)):
        store_doc = {"family": family.name, **doc}
        if mesh is not None:
            store_doc["meshAxes"] = {k: int(v) for k, v in mesh.shape.items()}
            store_doc["devices"] = int(np.prod(
                [int(v) for v in mesh.shape.values()]))
        store_as = dict(
            fingerprint="sweep-" + hashlib.sha256(json.dumps(
                store_doc, sort_keys=True).encode()).hexdigest()[:16],
            bucket=bucket, component="sweep", identity=identity)
        # a store hit (cross-process sweep cache: TG_AOT_STORE / a capture
        # scope) skips the trace; misses classify the build below as
        # aot-miss
        from ...programstore import store as _pstore
        fn = _pstore.lookup(store_as["fingerprint"], bucket,
                            component="sweep", ledger_key=key_hash)
        if fn is not None:
            _fused_cache_put(key, (fn, None))
            return fn, None, None, None
    garr_np = {k: np.asarray(v)
               for k, v in family.grid_to_arrays(grid).items()}
    t0_build = time.perf_counter()
    entry = _make_fused_program(
        family, garr_np, G, F, inputs.problem, inputs.metric_name,
        inputs.num_classes, inputs.exact, sliced, binned, mesh=mesh,
        x_ndim=X.ndim)
    _fused_cache_put(key, entry)
    _obs_ledger.record_build(
        "sweep", identity=identity, key=key_hash, fingerprint=doc,
        bucket=bucket, donation=entry[1],
        seconds=time.perf_counter() - t0_build, configs=G, folds=F)
    return (*entry, store_as, garr_np)


def _launch(family, grid, inputs: _SweepInputs, launches: List[str]):
    """One family's program on the device → a pending ``(name, grid, metric
    output, B_true, G)`` entry whose metrics nobody has waited for: every
    family's program queues on the device back to back and ONE sync reads
    all of them (a per-family sync costs a link round-trip each). A throw
    here (trace error, diverging fused fit, injected fault) quarantines the
    family, in ``validate``, instead of aborting the sweep. ``launches``
    gains the family's name: one entry a device program of the sweep."""
    # crash evidence: a kill past this point happened inside a fused sweep
    # dispatch (run sentinel, docs/robustness.md)
    sentinel_phase("device_sweep")
    launches.append(family.name)
    if getattr(family, "uses_hist_engine", False):
        # chaos site hist.build: a raise quarantines THIS family (same
        # recovery as validator.family_fit) before any of its histogram
        # programs build or dispatch
        from ...histeng import chaos_gate
        chaos_gate(family.name)
    X, mesh, F, G = inputs.X, inputs.mesh, inputs.F, len(grid)
    sliced = inputs.fold_sliced and getattr(family, "fold_sliced_predict",
                                            True)
    prog, grid_keys, store_as, garr_np = _program_for(family, grid, inputs,
                                                      sliced)
    args = [X, inputs.y, inputs.ids_d]
    if sliced:
        args += list(inputs.fold_data)
    if grid_keys is not None:
        # per-family scratch: the tiled grid packed into ONE (keys, F·G)
        # f32 block, uploaded sharded over 'model' and DONATED into the
        # program — one transfer per family and a buffer XLA may alias
        # instead of re-allocating. Never reused after the call (donation
        # safety).
        if garr_np is None:     # the program came from a cache
            garr_np = {k: np.asarray(v)
                       for k, v in family.grid_to_arrays(grid).items()}
        gb = np.stack([np.tile(garr_np[k], F) for k in grid_keys]
                      ).astype(np.float32)
        n_model = mesh.shape["model"]
        gb_pad = -(-gb.shape[1] // n_model) * n_model
        if gb_pad != gb.shape[1]:
            # zero-padded tail so the config axis divides the 'model'
            # shards; the program slices it off before the fit (an unpadded
            # block fails device_put outright)
            gb = np.pad(gb, ((0, 0), (0, gb_pad - gb.shape[1])))
        args.append(_put(jnp.asarray(gb), P(None, "model"), mesh,
                         "sweep.grid_upload"))
    # device-memory observatory: argument bytes plus the (F·G, n)
    # fold-weight tensor the trace builds on device — the branch's
    # dominant allocations, predicted before dispatch
    predicted = sum(_arg_nbytes(a) for a in args) + F * G * inputs.n_pad * 4
    _devicemem.record_dispatch("sweep", predicted, bucket=inputs.n_pad)
    with warnings.catch_warnings():
        # donated grid blocks too small for XLA to alias (tiny CPU grids)
        # emit a first-compile "donated buffers were not usable" warning —
        # expected, not actionable
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        # the engine mesh context must surround the TRACE — which happens
        # here, at the program's first call, not at _make_fused_program
        # (jit is lazy) — so tree histogram row blocks pin to the 'data'
        # axis (histeng.engine_mesh)
        with _hist_mesh_ctx(family, mesh):
            m = prog(*args)
    _devicemem.sample_measured("sweep")
    if store_as is not None:
        # populate: a freshly traced branch program is offered to the
        # active capture scopes / TG_AOT_STORE so the next process
        # deserializes instead of tracing (one flag check when nothing is
        # active). Export re-traces, so the engine mesh context applies
        # here too.
        from ...programstore import store as _pstore
        with _hist_mesh_ctx(family, mesh):
            _pstore.offer_segment(jitted_fn=prog, args=tuple(args),
                                  **store_as)
    return (family.name, list(grid), m, F * G, G)


def _launch_downshifting(family, grid, inputs: _SweepInputs,
                         launches: List[str]):
    """``_launch`` with adaptive degradation under memory pressure:
    resource exhaustion (XLA RESOURCE_EXHAUSTED / host MemoryError — or the
    ``oom.sweep`` chaos site) splits the packed (F·G) config grid in half
    and launches the halves as their own fused programs, recursively down
    to single configs; the per-config fold metrics merge back by
    concatenation along the config axis (each config's metric is
    independent of its batch-mates, so the merged (F, G) matrix is
    identical to the unsplit program's). The family is DOWNSHIFTED, not
    quarantined; only a single config that still exhausts — or any
    non-resource throw — propagates to the caller's quarantine handler."""
    try:
        faults.inject("oom.sweep", key=family.name)
        return _launch(family, grid, inputs, launches)
    except Exception as e:
        from ...robustness import resources
        if resources.classify_exhaustion(e) is None or len(grid) < 2:
            raise
        mid = len(grid) // 2
        resources.record_downshift(
            "oom.sweep", family=family.name, configs=len(grid),
            splitConfigs=[mid, len(grid) - mid],
            error=f"{type(e).__name__}: {e}"[:200])
        logger.warning(
            "sweep branch for %s exhausted memory at %d configs; "
            "splitting the grid into %d + %d",
            family.name, len(grid), mid, len(grid) - mid)
        F = inputs.F
        _, _, m1, _, G1 = _launch_downshifting(family, grid[:mid], inputs,
                                               launches)
        _, _, m2, _, G2 = _launch_downshifting(family, grid[mid:], inputs,
                                               launches)
        # metric monoid merge: un-pad each half to its (F, Gi) matrix and
        # concatenate along the config axis — the merged flat vector is
        # exactly the unsplit program's [:B_true] slice (_collect reshapes
        # it to (F, G))
        m = jnp.concatenate(
            [m1.reshape(-1)[:F * G1].reshape(F, G1),
             m2.reshape(-1)[:F * G2].reshape(F, G2)],
            axis=1).reshape(-1)
        return (family.name, list(grid), m, F * (G1 + G2), G1 + G2)


def _family_span_attrs(family, grid, inputs: _SweepInputs,
                       asked_mesh) -> Dict[str, Any]:
    """What a traced sweep.family span says of the fit's shape: what the
    family's own schedule fixes about it (contractions, chunks of lanes),
    read under the context the program is traced in; the mesh asked for,
    whether the cost model engaged it, and the rows of the table this
    program reads on a chip (a tree family's sweep fit reads its sample)."""
    X, F = inputs.X, inputs.F
    with _hist_mesh_ctx(family, inputs.mesh):
        own = family.fit_span_attrs(
            inputs.n_pad, int(X.shape[-1]), list(grid) * F,
            inputs.num_classes, not inputs.exact)
    return dict(
        classes=inputs.num_classes, lanes=F * len(grid), rows=inputs.n,
        features=int(X.shape[-1]),
        **mesh_span_attrs(asked_mesh, inputs.mesh is not None,
                          own.get("sampleRows", inputs.n_pad)),
        **own)


def _collect(pending, host_metrics: Dict[int, np.ndarray],
             fit_failures: Dict[int, str], all_m, F: int, metric_name: str,
             larger_better: bool) -> BestEstimator:
    """The one wait for the device sweep, the quarantine of non-finite
    configurations, and the winner. ``pending``: one ``_launch`` entry a
    family (its metric output None where ``host_metrics`` already holds the
    family's (F, G), or where its fit threw: ``fit_failures``); ``all_m``:
    every family's metric vector fused into ONE device array, so this pays a
    single blocking host transfer instead of one per family. Every list is
    built anew, so resolving a PendingValidation twice duplicates nothing."""
    results: List[ValidationResult] = []
    quarantined: List[Dict[str, Any]] = []
    best: Optional[BestEstimator] = None
    # the device->host metric fetch is the sweep's "transfer" phase,
    # timed into tg_sweep_transfer_seconds
    t0_fetch = time.perf_counter()
    # the one statement where the host waits for the device sweep
    with _obs_span("sweep.collect", cat="sweep", hbm=True,
                   families=sum(p[2] is not None for p in pending)):
        m_host = fetch_to_host(all_m) if all_m is not None else None
    if m_host is not None:
        _obs_metrics.observe(
            "tg_sweep_transfer_seconds", time.perf_counter() - t0_fetch,
            help="device->host validation-metric fetch per sweep")
    off = 0
    for fi, (fam_name, grid_l, m, B_true, G) in enumerate(pending):
        if fi in host_metrics:  # restored / eagerly persisted
            fold_metrics = host_metrics[fi]
        elif m is None:  # the family's fit threw before dispatch
            fold_metrics = np.full((F, G), np.nan, dtype=np.float64)
        else:
            m_fam = m_host[off:off + m.size]
            off += m.size
            fold_metrics = m_fam[:B_true].reshape(F, G)
        fold_metrics = faults.poison("validator.fold_metrics",
                                     fold_metrics, key=fam_name)
        # non-finite guard: quarantine diverged configs instead of
        # letting NaN elect itself (np.argmax ranks NaN as the max)
        mean_metrics, masked_means, records = quarantine_non_finite(
            fam_name, grid_l, fold_metrics, metric_name,
            larger_better, reason=fit_failures.get(fi))
        quarantined.extend(records)
        results.append(ValidationResult(
            family=fam_name, grid=grid_l, metric_name=metric_name,
            fold_metrics=fold_metrics, mean_metrics=mean_metrics))
        if not np.isfinite(mean_metrics).any():
            continue  # whole family quarantined
        g_best = int(np.argmax(masked_means) if larger_better
                     else np.argmin(masked_means))
        value = float(mean_metrics[g_best])
        better = best is None or (
            (value > best.metric_value) if larger_better
            else (value < best.metric_value))
        if better:
            best = BestEstimator(fam_name, dict(grid_l[g_best]), value)
    if best is None:
        raise AllCandidatesFailedError(quarantined)
    best.results = results
    best.quarantined = quarantined
    _obs_trace.add_event("sweep.winner", family=best.family_name,
                         metricValue=float(best.metric_value))
    return best


class OpValidator:
    """Shared validation machinery (reference OpValidator.scala).

    ``mesh``: optional ``jax.sharding.Mesh`` with ('data', 'model') axes —
    rows shard over 'data' and the config batch over 'model' (for families
    whose fit is a single vmapped program; sequential-scan families keep
    their configs whole and still get row sharding). The reference's analog
    is its 8-thread Future pool (OpValidator.scala:318-333); here the
    parallel axes are mesh axes and XLA inserts the psum collectives."""

    def __init__(self, seed: int = 42, stratify: bool = False, mesh=None,
                 max_eval_rows: "Optional[int]" = 32768,
                 exact_sweep_fits: bool = False, sweep_checkpoint=None):
        self.seed = seed
        self.stratify = stratify
        self.mesh = mesh
        #: fold-sliced validation scoring evaluates each configuration on at
        #: most this many of its fold's rows (deterministic strided
        #: subsample). Metric ESTIMATES only — refit, holdout and train
        #: evaluations always use full data. None = score every validation
        #: row (exact reference parity); the default scores a 32768-row
        #: sample of each fold, so the sweep's predict cost stops growing
        #: with the table; tests/test_round3_fixes.py holds its ranking
        #: against the exact setting's.
        self.max_eval_rows = max_eval_rows
        #: True = CV candidates fit through ``fit_batch`` (full precision /
        #: full split-search sample) instead of ``sweep_fit_batch``'s
        #: throughput approximations — exact reference semantics
        #: (OpValidator.getSummary:270-312 full-data fits) at several times
        #: the sweep cost
        self.exact_sweep_fits = exact_sweep_fits
        #: a :class:`~.sweep_checkpoint.SweepCheckpoint` (the workflow wires
        #: one through ``ModelSelector.set_sweep_checkpoint``): every
        #: family's fold metrics are persisted as the family completes, and
        #: a resumed sweep replays the records that match its data, folds
        #: and configuration. Train-time wiring; None: no checkpointing.
        self.sweep_checkpoint = sweep_checkpoint
        #: wiring attrs: where the last ENGAGED mesh sweep placed its table
        #: (None until one ran) and the (device, shard shape) of every shard
        #: it held — lets a multi-chip check assert every device held
        #: shards, like SanityChecker._stats_input_sharding
        self.last_sweep_sharding = None
        self.last_sweep_shards = None

    # -- fold construction ---------------------------------------------------
    def make_splits(self, y: np.ndarray) -> np.ndarray:
        """(F, n) boolean VALIDATION masks; train mask = ~val."""
        raise NotImplementedError

    def _kfold_masks(self, y: np.ndarray, k: int) -> np.ndarray:
        n = len(y)
        rng = np.random.RandomState(self.seed)
        masks = np.zeros((k, n), dtype=bool)
        if self.stratify:
            # per-class round-robin folds (reference stratified kFold union
            # OpCrossValidation.scala:139-181)
            for lab in np.unique(y):
                idx = np.nonzero(y == lab)[0]
                idx = rng.permutation(idx)
                for f in range(k):
                    masks[f, idx[f::k]] = True
        else:
            perm = rng.permutation(n)
            for f in range(k):
                masks[f, perm[f::k]] = True
        return masks

    # -- the sweep -----------------------------------------------------------
    def validate(self, models: Sequence[Tuple[ModelFamily, List[Dict[str, Any]]]],
                 X: jnp.ndarray, y: jnp.ndarray, problem: str,
                 metric_name: str, larger_better: bool, num_classes: int,
                 val_masks: Optional[np.ndarray] = None,
                 fold_sliced: Optional[bool] = None,
                 resolve: bool = True,
                 padded_rows: Optional[int] = None):
        """Run the full |families| × |grid| × |folds| sweep. Each family is one
        vmapped fit_batch + predict_batch + batched-metric program.

        ``val_masks`` overrides the fold construction with explicit (F, n)
        boolean validation masks — used by the workflow-level CV path, which
        must evaluate one externally-prepared fold at a time. ``fold_sliced``
        forces the per-fold row-gather scoring path on/off (default: on —
        under a mesh the gathered fold tensors are re-sharded over 'data').

        ``padded_rows``: ``X`` already has that many rows, zeros past
        ``len(y)``, and it is the sweep's padded size as it stands (the
        selector under a mesh gathers its rows straight into the bucket);
        None: ``X`` has ``len(y)`` rows and is padded here."""
        if val_masks is None:
            val_masks = self.make_splits(np.asarray(y))  # (F, n)
        vm_np = np.asarray(val_masks)
        F, n = vm_np.shape
        exact, ckpt = self.exact_sweep_fits, self.sweep_checkpoint
        # fingerprint this run BEFORE padding so a persisted candidate
        # record can only replay onto identical data/folds/config
        fingerprint = None if ckpt is None else sweep_fingerprint(
            X, y, vm_np, problem=problem, metric_name=metric_name,
            num_classes=num_classes, larger_better=larger_better,
            exact=exact, max_eval_rows=self.max_eval_rows)
        mesh, X, y = _engage_mesh(self.mesh, X, y, n,
                                  F * sum(len(g) for _, g in models))
        inputs = _place_inputs(
            X, y, vm_np, mesh, padded_rows, self.max_eval_rows,
            problem=problem, metric_name=metric_name,
            num_classes=num_classes, exact=exact,
            fold_sliced=True if fold_sliced is None else fold_sliced)
        if mesh is not None:
            self.last_sweep_sharding = inputs.X.sharding
            self.last_sweep_shards = [
                (sh.device, tuple(sh.data.shape))
                for sh in inputs.X.addressable_shards]
        #: the family of every device program launched (one a family unless
        #: the exhaustion ladder split a grid): a span's ``programs``
        launches: List[str] = []
        order = 0       # families whose span has closed: not the restored
        # per-candidate quarantine at family granularity: a family's whole
        # branch is one fused program, so a throw poisons all its configs —
        # record the reason, keep a placeholder with no metric output, and
        # go on with the other families (the reference survives this via
        # Spark task retries + lineage); _collect raises if none is left
        pending: List[Any] = []
        fit_failures: Dict[int, str] = {}
        #: (F, G) metrics already on the host, by family index: a restored
        #: family's, and every family's under a checkpoint (see below)
        host_metrics: Dict[int, np.ndarray] = {}
        for fi, (family, grid) in enumerate(models):
            grid = list(grid)
            placeholder = (family.name, grid, None, F * len(grid), len(grid))
            ckey = restored = None
            if ckpt is not None:
                ckey = candidate_key(family.name, grid, fingerprint)
                restored = ckpt.restore(ckey, F, len(grid))
            if restored is not None:
                host_metrics[fi], reason = restored
                if reason is not None:
                    fit_failures[fi] = reason
                pending.append(placeholder)
                continue
            # sweep span per candidate family: grid size, folds, metric,
            # and the compile-cache hit/miss delta of dispatching this
            # branch (utils/jax_cache.py listener): compile vs execute
            with _obs_span("sweep.family", cat="sweep", family=family.name,
                           configs=len(grid), folds=F, metric=metric_name,
                           order=order, hbm=True) as sweep_span:
                launched_before = len(launches)
                if tracing_enabled():
                    sweep_span.set_attr(**_family_span_attrs(
                        family, grid, inputs, self.mesh))
                # flight-recorder: each family dispatch, stamped with the
                # owning run's correlation id (workflow.train) — a sweep
                # post-mortem shows which family the incident interrupted
                _blackbox.record("sweep.family", family=family.name,
                                 configs=len(grid), folds=F)
                cs0 = cache_stats() if tracing_enabled() else None
                try:
                    # deterministic preemption point: the process dies
                    # between family branches — already-persisted
                    # candidates survive
                    faults.inject("preempt.sweep", key=family.name)
                    faults.inject("validator.family_fit", key=family.name)
                    pending.append(_launch_downshifting(
                        family, grid, inputs, launches))
                except Exception as e:
                    reason = f"fit raised {type(e).__name__}: {e}"
                    logger.warning("quarantining model family %s: %s",
                                   family.name, reason)
                    pending.append(placeholder)
                    fit_failures[fi] = reason
                    sweep_span.add_event("sweep.family_quarantined",
                                         family=family.name, reason=reason)
                sweep_span.set_attr(
                    programs=len(launches) - launched_before)
                if cs0 is not None:
                    cs1 = cache_stats()
                    sweep_span.set_attr(
                        cacheHits=cs1["hits"] - cs0["hits"],
                        cacheMisses=cs1["misses"] - cs0["misses"])
            order += 1
            if ckpt is not None:
                # durability costs the single-sync batching: each family's
                # metrics must reach the host — and disk — before the next
                # family runs, or a preemption loses them
                _, _, m, B_true, G = pending[-1]
                fm_host = np.full((F, G), np.nan)
                if m is not None:
                    fm_host = host_metrics[fi] = np.asarray(
                        fetch_to_host(m)).reshape(-1)[:B_true].reshape(F, G)
                    # drop the device handle: _collect reads the host copy
                    pending[-1] = placeholder
                ckpt.persist(ckey, family.name, grid, metric_name, fm_host,
                             fit_failures.get(fi))
        # fuse every family's metric vector into ONE device array so
        # _collect pays a single blocking host transfer
        valid_m = [p[2].reshape(-1) for p in pending if p[2] is not None]
        all_m = (jnp.concatenate(valid_m) if len(valid_m) > 1
                 else valid_m[0] if valid_m else None)
        finish = functools.partial(_collect, pending, host_metrics,
                                   fit_failures, all_m, F, metric_name,
                                   larger_better)
        return finish() if resolve else PendingValidation(finish)


class OpCrossValidation(OpValidator):
    """k-fold CV (reference OpCrossValidation.scala, default 3 folds)."""

    def __init__(self, num_folds: int = 3, **kw):
        super().__init__(**kw)
        if num_folds < 2:
            raise ValueError("num_folds must be >= 2")
        self.num_folds = num_folds

    def make_splits(self, y: np.ndarray) -> np.ndarray:
        return self._kfold_masks(y, self.num_folds)


class OpTrainValidationSplit(OpValidator):
    """Single train/validation split (reference OpTrainValidationSplit.scala,
    default ratio 0.75)."""

    def __init__(self, train_ratio: float = 0.75, **kw):
        super().__init__(**kw)
        if not 0.0 < train_ratio < 1.0:
            raise ValueError("train_ratio must be in (0, 1)")
        self.train_ratio = train_ratio

    def make_splits(self, y: np.ndarray) -> np.ndarray:
        n = len(y)
        rng = np.random.RandomState(self.seed)
        val = np.zeros((1, n), dtype=bool)
        if self.stratify:
            for lab in np.unique(y):
                idx = rng.permutation(np.nonzero(y == lab)[0])
                n_val = int(round(len(idx) * (1.0 - self.train_ratio)))
                val[0, idx[:n_val]] = True
        else:
            perm = rng.permutation(n)
            val[0, perm[: int(round(n * (1.0 - self.train_ratio)))]] = True
        return val
