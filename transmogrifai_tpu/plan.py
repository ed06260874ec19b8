"""Transform-plan compiler: one XLA program per device-fusable segment.

The paper's substrate swap is "jit-compiled kernels instead of Catalyst";
the model selector fuses each family's sweep glue into a single jitted
program (impl/tuning/validators.py). This module applies the same
cure to the fit-and-transform DAG: instead of dispatching every transformer
as its own executable (each a separate launch with its own dispatch
bubble), a *plan* partitions a topologically-ordered run of
fitted/pure transformer stages into maximal device-fusable segments — stages
exposing a pure-jax ``device_columnar`` dual — separated by host stages
(object-array text/map fronts, row lambdas), and traces each segment into
ONE jitted program. XLA then fuses across stage boundaries and dead-code
eliminates intermediates nobody reads — the reference's
``applyOpTransformations`` layer fusion (FitStagesUtil.scala:96-119) and
whole-stage-codegen idea, landed on our side of the swap.

Consumers: ``fit_and_transform_dag`` (each layer's transformer run),
``apply_transformations_dag`` (→ ``OpWorkflow.score()``), and
``local/scoring.compiled_score_function`` (→ ``micro_batch_score_function``)
all call :func:`apply_planned`. Plans are cached in a bounded LRU
(``TG_PLAN_CACHE_MAX``, defaulting to the validators' ``_FUSED_CACHE``
bound) keyed by stage-uid sequence + input schema fingerprint.

Robustness interplay is part of the design, not an afterthought
(docs/plan.md "Fallback semantics"):

* planning is skipped outright when per-stage fault semantics are active —
  ``OpWorkflow.with_fault_policy()`` (the caller passes eager) or
  ``TG_CHAOS`` / armed non-``plan.*`` injection sites — so PR 1's per-stage
  retry/quarantine behavior is byte-for-byte preserved under chaos;
* a planned run that *raises* (including the ``plan.segment_execute``
  injection site) falls back to eager per-stage dispatch for that run, and
  the fallback is recorded as a FaultLog ``plan_fallback`` event + span
  event — never silent.

Observability: ``plan.compile`` / ``plan.execute`` / ``plan.segment`` spans,
the ``tg_dispatch_total`` counter (top-level device executable launches:
one per device-capable stage in eager mode, one per fused segment planned)
and ``tg_device_transfer_total`` (host→device uploads). All zero-write when
observability is off. Every plan build and every per-bucket first dispatch
is additionally reported to the compile ledger with a classified cause
(cold / schema-change / bucket-change / cache-eviction), and every segment
dispatch reports its shape-predicted device bytes to the memory
observatory (observability/ledger.py, observability/devicemem.py —
docs/observability.md "Compile & memory ledger").
"""
from __future__ import annotations

import hashlib
import logging
import os
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .observability import devicemem as _devicemem
from .observability import ledger as _ledger
from .observability import metrics as _obs_metrics
from .observability.trace import span as _obs_span
from .table import Column, FeatureTable

logger = logging.getLogger(__name__)

#: ``enable_planning(False)`` runs the eager per-stage path (the tests' and
#: the benchmark's bit-equality reference); None or True plans
_enabled_override: Optional[bool] = None

#: plan LRU: (stage identity seq, schema fp, options) → TransformPlan | None
#: (None caches "planning infeasible for this shape" so the probe cost is
#: paid once). Bounded like the validators' _FUSED_CACHE: each entry pins
#: jitted executables, so a long-lived server fitting many schemas must not
#: grow compiled-program memory without bound.
_PLAN_CACHE: "OrderedDict[Any, Optional[TransformPlan]]" = OrderedDict()
_PLAN_CACHE_MAX = int(os.environ.get(
    "TG_PLAN_CACHE_MAX", os.environ.get("TG_FUSED_CACHE_MAX", "32")))


def plan_enabled() -> bool:
    """True when the transform-plan compiler may be used: always, unless
    ``enable_planning(False)`` turned it off."""
    return _enabled_override is None or _enabled_override


def enable_planning(on: Optional[bool]) -> None:
    """Force planning on/off from code (tests, the benchmark's eager
    reference); ``None`` restores the default, which is on."""
    global _enabled_override
    _enabled_override = None if on is None else bool(on)


def planning_applicable() -> bool:
    """Planning is allowed only when per-stage fault semantics are not in
    play: under ``TG_CHAOS``, or with any armed injection site that is not
    registered as ``keeps_planner`` (robustness/faults.py ``SiteSpec``: the
    sites that target the planner itself, the layers above it or its own
    dispatch), the eager per-stage path runs, so the per-stage
    retry/quarantine behavior is exactly preserved. An armed name the
    registry does not hold counts as a per-stage site."""
    if not plan_enabled():
        return False
    from .robustness import faults
    if os.environ.get(faults.CHAOS_ENV):
        return False
    return all(s in faults.ALL_SITES and faults.ALL_SITES[s].keeps_planner
               for s in faults.active_sites())


def clear_plan_cache() -> None:
    """Drop every cached plan (test isolation; see tests/conftest.py)."""
    _PLAN_CACHE.clear()


def cache_stats() -> Dict[str, int]:
    """{"entries", "max"} — surfaced in summary()["observability"]."""
    return {"entries": len(_PLAN_CACHE), "max": _PLAN_CACHE_MAX}


# ---------------------------------------------------------------------------
# Stage classification
# ---------------------------------------------------------------------------

def is_device_capable(stage: Any) -> bool:
    """A stage that exposes the pure-jax columnar dual and has not opted out
    dynamically (e.g. a SelectedModel whose family has no traceable
    predict)."""
    return (hasattr(stage, "device_columnar")
            and getattr(stage, "device_fusable", True))


def count_eager_dispatch(stage: Any) -> None:
    """Account one eager transform of a device-capable stage. Eager (unjitted)
    columnar execution launches at least one executable per input column
    chain — op-by-op dispatch never fuses across columns — so the counter
    adds ``max(1, |device inputs|)``: a conservative lower bound of the
    launches the fused segment replaces with ONE (docs/plan.md)."""
    if not is_device_capable(stage):
        return
    _obs_metrics.inc_counter(
        "tg_dispatch_total", float(max(1, len(_device_inputs(stage)))),
        kind="stage",
        help="top-level device executable launches on the transform path "
        "(docs/plan.md)")


def _device_inputs(stage: Any) -> List[str]:
    if hasattr(stage, "device_inputs"):
        return list(stage.device_inputs())
    return [f.name for f in stage.input_features]


def _host_inputs(stage: Any) -> List[str]:
    return [f.name for f in stage.input_features]


def _numeric_table_col(col: Column) -> bool:
    dt = getattr(col.values, "dtype", None)
    return dt is not None and np.dtype(dt).kind in "fiub"


# ---------------------------------------------------------------------------
# Plan structure
# ---------------------------------------------------------------------------

class _DeviceSegment:
    """One maximal run of device-fusable stages traced into one jitted
    program. ``in_names`` are the columns the program reads (external to the
    segment), ``out_names`` the columns it materializes."""

    __slots__ = ("stages", "in_names", "out_names", "chain", "out_meta",
                 "out_shape", "in_shape", "seen_buckets", "fp_key",
                 "pred_cache", "aot_progs")

    def __init__(self, stages: List[Any], in_names: List[str],
                 out_names: List[str]):
        self.stages = stages
        self.in_names = in_names
        self.out_names = out_names
        self.out_meta: Dict[str, Tuple[Any, Dict[str, Any]]] = {}
        #: output column (itemsize, trailing shape) from the zero-row
        #: probe — what the byte prediction needs (devicemem)
        self.out_shape: Dict[str, Tuple[int, Tuple[int, ...]]] = {}
        #: input column trailing shapes from the zero-row probe — enough
        #: to reconstruct the traced avals at any padding bucket (staged
        #: inputs are always f32 values + a bool mask), which is what
        #: AOT export needs without a live dispatch (programstore/)
        self.in_shape: Dict[str, Tuple[int, ...]] = {}
        #: padding buckets this segment's jitted chain has already been
        #: dispatched at: the first dispatch of a NEW bucket is an XLA
        #: compile, recorded in the compile ledger
        self.seen_buckets: set = set()
        #: lazily-computed segment fingerprint hash (the cost-table key;
        #: cached — the serving hot path dispatches this per flush)
        self.fp_key: Optional[str] = None
        #: bucket → predicted bytes (schema-fixed per plan, so one
        #: computation per bucket serves every later dispatch)
        self.pred_cache: Dict[int, int] = {}
        #: bucket → AOT-deserialized program (programstore/store.py):
        #: dispatched INSTEAD of tracing ``chain`` — the zero-retrace
        #: cold-start path (docs/serving.md "AOT cold start")
        self.aot_progs: Dict[int, Any] = {}
        import jax
        fused = list(stages)
        outs = list(out_names)

        @jax.jit
        def chain(vals_list, mask_list):
            env = {nm: (v, m)
                   for nm, v, m in zip(in_names, vals_list, mask_list)}
            for s in fused:
                # trace-time name only (the module stays ``jit_chain``)
                with jax.named_scope(f"stage.{type(s).__name__}"):
                    env[s.get_output().name] = s.device_columnar(env)
            return tuple(env[nm] for nm in outs)

        self.chain = chain


#: executables of train-time segments by the text of their lowered program
_TRAIN_SEGMENT_PROGRAMS: "OrderedDict[str, Any]" = OrderedDict()
_TRAIN_SEGMENT_PROGRAMS_MAX = 64


def _train_segment_program(chain, vals, masks) -> Tuple[Any, bool]:
    """The executable of a train-time segment's ``chain`` at these
    arguments, and whether an earlier train had already built it.

    A train-time layer's stages are new objects at every ``train()``, so
    their plan, its segments and the jitted ``chain`` are new as well: jit's
    own cache cannot find last train's executable, and the same program was
    compiled again inside every train of the same table (PR 30 found it
    where a Real and an Integral vectorizer share a layer). The lowered
    program's text says all that the executable depends on (the stages'
    classes, their fitted fills, the shapes and shardings; no uid), so the
    executable is kept under its hash, at most
    ``_TRAIN_SEGMENT_PROGRAMS_MAX`` of them."""
    lowered = chain.lower(vals, masks)
    key = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    prog = _TRAIN_SEGMENT_PROGRAMS.get(key)
    if prog is not None:
        _TRAIN_SEGMENT_PROGRAMS.move_to_end(key)
        return prog, True
    prog = _TRAIN_SEGMENT_PROGRAMS[key] = lowered.compile()
    while len(_TRAIN_SEGMENT_PROGRAMS) > _TRAIN_SEGMENT_PROGRAMS_MAX:
        _TRAIN_SEGMENT_PROGRAMS.popitem(last=False)
    return prog, False


class TransformPlan:
    """An executable schedule: alternating host waves (eager per-stage
    dispatch) and device segments (one jitted program each)."""

    def __init__(self, steps: List[Tuple[str, Any]], cat: str):
        self.steps = steps
        self.cat = cat
        #: stable program identity (stage-uid sequence) + JSON schema
        #: fingerprint, set by get_plan — the compile ledger's
        #: classification baseline (observability/ledger.py)
        self.ident: str = "plan"
        self.fp_json: Any = None
        #: process-independent hash of (ident × schema fingerprint) —
        #: the AOT program store's plan-coverage key (stage uids survive
        #: save/load, so a fresh process computes the same hash;
        #: programstore/store.py)
        self.ident_hash: Optional[str] = None

    @property
    def num_segments(self) -> int:
        return sum(1 for k, _ in self.steps if k == "device")

    @property
    def num_host_stages(self) -> int:
        return sum(len(p) for k, p in self.steps if k == "host")

    def device_table_inputs(self, table: FeatureTable) -> List[str]:
        """Segment inputs that come straight from the caller's table (the
        user-input surface serve-time schema guards validate)."""
        produced = {s.get_output().name
                    for k, p in self.steps
                    for s in (p if k == "host" else p.stages)}
        out: List[str] = []
        for k, p in self.steps:
            if k != "device":
                continue
            for nm in p.in_names:
                if nm not in produced and nm in table and nm not in out:
                    out.append(nm)
        return out

    # -- execution -----------------------------------------------------------
    def execute(self, table: FeatureTable) -> FeatureTable:
        with _obs_span("plan.execute", cat=self.cat, rows=table.num_rows,
                       segments=self.num_segments,
                       hostStages=self.num_host_stages):
            seg_idx = 0
            for kind, payload in self.steps:
                if kind == "host":
                    for s in payload:
                        # a device-capable stage demoted to host (non-
                        # numeric inputs) still launches eager programs
                        count_eager_dispatch(s)
                        with _obs_span("stage.transform", cat=self.cat,
                                       hbm=True,
                                       uid=getattr(s, "uid", "?"),
                                       stage=type(s).__name__, planned=True):
                            table = s.transform(table)
                else:
                    table = self._run_segment(payload, table, seg_idx)
                    seg_idx += 1
        return table

    def _predicted_bytes(self, seg: _DeviceSegment, table: FeatureTable,
                         n_pad: int) -> int:
        """Shape-predicted device bytes of one padded segment dispatch:
        every input column staged at the bucket (f32 + bool mask) plus
        every materialized output at its probe-captured shape — the
        number admission control can subtract from the device budget
        before dispatch (observability/devicemem.py)."""
        from .utils.padding import padded_bytes
        total = 0
        for nm in seg.in_names:
            v = table[nm].values
            total += padded_bytes(n_pad, tuple(np.shape(v)[1:]), 4)
        for nm in seg.out_names:
            itemsize, trailing = seg.out_shape.get(nm, (4, ()))
            total += padded_bytes(n_pad, trailing, itemsize)
        return total

    def _run_segment(self, seg: _DeviceSegment,
                     table: FeatureTable, seg_idx: int = 0) -> FeatureTable:
        import jax.numpy as jnp

        from .manifest import sentinel_phase
        from .robustness import faults
        from .utils.padding import bucket_for
        # crash evidence: if the process dies past this point the run
        # sentinel says it was inside a device dispatch (OOM-kill suspect)
        sentinel_phase("device_dispatch")
        # deterministic chaos entry: a fault here models an XLA runtime
        # error mid-plan; apply_planned catches it and falls back to eager
        faults.inject("plan.segment_execute", key=seg.stages[0].uid)
        # chaos: a RESOURCE_EXHAUSTED here models the padded segment not
        # fitting on the device; apply_planned bisects the row batch to
        # smaller padding buckets before falling back to eager
        faults.inject("oom.plan", key=seg.stages[0].uid)
        n = table.num_rows
        n_pad = bucket_for(n)
        t0 = (time.perf_counter()
              if _obs_metrics.metrics_enabled() else None)
        transfers = 0
        nbytes = 0          # host bytes handed to the device (h2d)
        vals_list, mask_list = [], []
        # cast, pad and upload of the segment's inputs: launches only, the
        # uploads complete behind the dispatch below
        with _obs_span("plan.stage_inputs", cat=self.cat,
                       bucket=n_pad) as stage_span:
            for nm in seg.in_names:
                col = table[nm]
                v, m = col.values, col.mask
                if isinstance(v, np.ndarray):
                    v = np.asarray(v, dtype=np.float32)
                    if n_pad != n:
                        v = np.concatenate(
                            [v, np.zeros((n_pad - n,) + v.shape[1:],
                                         v.dtype)])
                    m = self._pad_mask_host(m, n, n_pad)
                    nbytes += v.nbytes + m.nbytes
                    v, m = jnp.asarray(v), jnp.asarray(m)
                    transfers += 2
                else:
                    if v.dtype != jnp.float32:
                        v = v.astype(jnp.float32)
                    if n_pad != n:
                        v = jnp.pad(v, ((0, n_pad - n),)
                                    + ((0, 0),) * (v.ndim - 1))
                    if m is None:
                        m = self._pad_mask_host(None, n, n_pad)
                        nbytes += m.nbytes
                        m = jnp.asarray(m)
                        transfers += 1
                    else:
                        if isinstance(m, np.ndarray):
                            nbytes += m.nbytes
                        m = jnp.asarray(m)
                        if n_pad != n:
                            m = jnp.pad(m, (0, n_pad - n))
                vals_list.append(v)
                mask_list.append(m)
            stage_span.set_attr(bytes=nbytes, transfers=transfers)
        if t0 is not None:
            _obs_metrics.observe(
                "tg_plan_transfer_seconds", time.perf_counter() - t0,
                help="host→device input staging per planned segment")
            _obs_metrics.inc_counter(
                "tg_device_transfer_total", float(transfers),
                help="host→device uploads (packed: see docs/plan.md)")
            _obs_metrics.inc_counter(
                "tg_transfer_bytes_total", float(nbytes), direction="h2d",
                help="bytes moved across the host<->device link")
        _obs_metrics.inc_counter(
            "tg_dispatch_total", kind="plan_segment",
            help="top-level device executable launches on the transform "
            "path (docs/plan.md)")
        # compile & memory observatory: shape-predicted bytes before the
        # dispatch, per-bucket first-call compiles into the ledger, the
        # (segment fingerprint x bucket) cost row after
        subsystem = _ledger.current_subsystem("plan")
        predicted = seg.pred_cache.get(n_pad)
        if predicted is None:
            # one shape computation per (plan, bucket): the plan's schema
            # is fixed by its cache key, so later dispatches reuse it
            predicted = self._predicted_bytes(seg, table, n_pad)
            seg.pred_cache[n_pad] = predicted
        _devicemem.record_dispatch(subsystem, predicted, bucket=n_pad,
                                   rows=n)
        first_bucket = n_pad not in seg.seen_buckets
        if seg.fp_key is None:
            seg.fp_key = _ledger.cache_key_hash(
                (self.ident, seg_idx, tuple(seg.in_names),
                 tuple(seg.out_names), self.fp_json))
        seg_fp = seg.fp_key
        # AOT program store: the first dispatch at a new bucket asks the
        # open store sessions for a deserialized program BEFORE tracing
        # the jitted chain — the zero-retrace cold-start path. Any miss
        # (absent / key mismatch / corrupt / injected) degrades to the
        # trace below with a typed record (programstore/store.py).
        aot_fn = seg.aot_progs.get(n_pad)
        if aot_fn is None and first_bucket:
            from .programstore import store as _pstore
            aot_fn = _pstore.lookup(seg_fp, n_pad,
                                    component="plan-segment",
                                    ledger_key=f"{seg_fp}@{n_pad}")
            if aot_fn is not None:
                seg.aot_progs[n_pad] = aot_fn
        pre_stats = _devicemem.memory_stats()
        t_disp = time.perf_counter()
        reused = False
        with _obs_span("plan.segment", cat=self.cat,
                       stages=len(seg.stages), rows=n,
                       inputs=len(seg.in_names), outputs=len(seg.out_names),
                       aot=aot_fn is not None):
            run = aot_fn or seg.chain
            if aot_fn is None and first_bucket and self.cat == "train":
                run, reused = _train_segment_program(
                    seg.chain, tuple(vals_list), tuple(mask_list))
                seg.aot_progs[n_pad] = run
            outs = run(tuple(vals_list), tuple(mask_list))
        disp_secs = time.perf_counter() - t_disp
        post_stats = _devicemem.sample_measured(subsystem)
        # cost bytes: measured allocation delta where the backend reports
        # live-buffer stats, shape-predicted otherwise (CPU)
        cost_bytes = predicted
        if pre_stats is not None and post_stats is not None:
            delta = (post_stats.get("bytes_in_use", 0)
                     - pre_stats.get("bytes_in_use", 0))
            if delta > 0:
                cost_bytes = delta
        if first_bucket:
            seg_ident = f"{self.ident}/seg{seg_idx}"
            seg.seen_buckets.add(n_pad)
            if aot_fn is not None or reused:
                # AOT hit (or an earlier train's executable of the same
                # program): nothing was compiled — no ledger build. The
                # dispatch still lands a cost row (execute side) so the
                # admission table stays warm.
                _devicemem.record_cost(seg_fp, n_pad, cost_bytes,
                                       execute_s=disp_secs)
            else:
                # the first dispatch at a NEW padding bucket
                # traces+compiles a fresh XLA executable inside the
                # jitted chain — that IS a program build (cold for the
                # first bucket, bucket-change when row growth crossed a
                # bucket boundary, aot-miss when a store should have
                # served it)
                _ledger.record_build(
                    subsystem, identity=seg_ident,
                    key=f"{seg_fp}@{n_pad}", fingerprint=self.fp_json,
                    bucket=n_pad, seconds=disp_secs, rows=n,
                    stages=len(seg.stages), cat=self.cat)
                _devicemem.record_cost(seg_fp, n_pad, cost_bytes,
                                       compile_s=disp_secs)
                # populate: offer the freshly traced program to any
                # active capture scope / cross-model store so the NEXT
                # process (or replica) deserializes instead of tracing
                from .programstore import store as _pstore
                _pstore.offer_segment(
                    seg_fp, n_pad, seg.chain,
                    (tuple(vals_list), tuple(mask_list)),
                    component="plan-segment", identity=seg_ident,
                    plan_ident=self.ident_hash)
        else:
            _devicemem.record_cost(seg_fp, n_pad, cost_bytes,
                                   execute_s=disp_secs)
        new_cols: Dict[str, Column] = {}
        # un-padding: the first ``np.asarray(msk)`` is where the host waits
        # for the segment's program on the device
        with _obs_span("plan.collect", cat=self.cat,
                       outputs=len(seg.out_names)):
            for nm, (arr, msk) in zip(seg.out_names, outs):
                # slice padding back off; keep values device-resident
                # (exactly what the eager fused-substrate stages hand
                # downstream)
                msk_np = None if msk is None else np.asarray(msk)[:n]
                if msk_np is not None and msk_np.all():
                    msk_np = None
                ftype, md = seg.out_meta[nm]
                new_cols[nm] = Column(ftype, arr[:n], msk_np, dict(md))
        return table.with_columns(new_cols)

    @staticmethod
    def _pad_mask_host(m, n: int, n_pad: int) -> np.ndarray:
        """Masks always materialize as bool arrays (padding rows False) so
        the traced program has one stable structure across batch sizes."""
        out = np.zeros(n_pad, dtype=bool)
        if m is None:
            out[:n] = True
        else:
            out[:n] = np.asarray(m)
        return out


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------

def _build_plan(stages: List[Any], table: FeatureTable,
                keep_intermediates: bool, extra_keep: Sequence[str],
                cat: str) -> Optional[TransformPlan]:
    """Partition ``stages`` (topological order) into host waves and device
    segments, trace each segment, and probe metadata. Returns None when the
    sequence has nothing worth fusing."""
    producer: Dict[str, Any] = {}      # column name → producing stage
    is_dev: Dict[int, bool] = {}
    numeric: Dict[str, bool] = {}      # column name → float32-convertible
    for nm in table.column_names:
        numeric[nm] = _numeric_table_col(table[nm])

    from .table import DEVICE_KINDS
    for s in stages:
        dev = is_device_capable(s)
        if dev:
            # demote to host when any runtime input is non-numeric for the
            # fused program (e.g. a vectorizer front over object arrays)
            for nm in _device_inputs(s):
                if not numeric.get(nm, False):
                    dev = False
                    break
        is_dev[id(s)] = dev
        out = s.get_output()
        producer[out.name] = s
        numeric[out.name] = (dev
                             or out.feature_type.column_kind in DEVICE_KINDS)
    if not any(is_dev[id(s)] for s in stages):
        return None        # nothing to fuse — eager is already minimal

    # wave assignment: host wave w runs before device segment w; a stage
    # lands in the earliest slot its producers allow, so device segments are
    # maximal (stages fuse across interleaved-but-independent host stages)
    wave: Dict[int, int] = {}
    for s in stages:
        dev = is_dev[id(s)]
        ins = _device_inputs(s) if dev else _host_inputs(s)
        w = 0
        for nm in ins:
            p = producer.get(nm)
            if p is None:
                continue
            pw = wave[id(p)]
            # host wave w runs before device segment w, so only the
            # device→host crossing forces the consumer into the next wave
            w = max(w, pw + 1 if (is_dev[id(p)] and not dev) else pw)
        wave[id(s)] = w

    max_wave = max(wave.values()) if wave else 0
    sched: List[Tuple[str, List[Any]]] = []
    for w in range(max_wave + 1):
        host = [s for s in stages if not is_dev[id(s)] and wave[id(s)] == w]
        if host:
            sched.append(("host", host))
        dev_stages = [s for s in stages if is_dev[id(s)] and wave[id(s)] == w]
        # fusion barriers (reduction-bearing stages like the winning
        # model's Prediction emission) trace into their OWN program: a
        # reduction's summation order is only reproducible when its operand
        # arrives as a program parameter, so fusing it mid-segment would
        # break the planned≡eager bit-exactness contract (docs/plan.md)
        run: List[Any] = []
        for s in dev_stages:
            if getattr(s, "device_fusion_barrier", False):
                if run:
                    sched.append(("dev", run))
                    run = []
                sched.append(("dev", [s]))
            else:
                run.append(s)
        if run:
            sched.append(("dev", run))

    steps: List[Tuple[str, Any]] = []
    for i, (kind, group) in enumerate(sched):
        if kind == "host":
            steps.append(("host", group))
            continue
        seg_out = {s.get_output().name for s in group}
        in_names: List[str] = []
        for s in group:
            for nm in _device_inputs(s):
                if nm not in seg_out and nm not in in_names:
                    in_names.append(nm)
        if keep_intermediates:
            out_names = [s.get_output().name for s in group]
        else:
            # materialize only what escapes the segment: XLA DCE's the rest
            ext = set(extra_keep)
            for _, later in sched[i + 1:]:
                for t in later:
                    ext.update(_device_inputs(t) if is_dev[id(t)]
                               else _host_inputs(t))
            out_names = [s.get_output().name for s in group
                         if s.get_output().name in ext]
            if not out_names:
                continue   # fully dead segment: plan-level DCE, skip it
        steps.append(("device", _DeviceSegment(group, in_names, out_names)))

    if not any(k == "device" for k, _ in steps):
        return None        # DCE dropped every segment — plan is all-host
    plan = TransformPlan(steps, cat)

    # zero-row probe: output feature types + metadata are data-independent
    # (fill/pivot/slice provenance comes from fitted state and input
    # *metadata*, never values), so one eager pass over an empty table
    # captures them without paying a real eager run
    read_names: List[str] = []
    produced = {s.get_output().name for s in stages}
    for s in stages:
        for nm in set(_host_inputs(s)) | set(_device_inputs(s)):
            if nm not in produced and nm in table and nm not in read_names:
                read_names.append(nm)
    probe_cols: Dict[str, Column] = {}
    for nm in read_names:
        col = table[nm]
        v = col.values
        dt = np.dtype(getattr(v, "dtype", object))
        trailing = tuple(int(x) for x in v.shape[1:])
        probe_cols[nm] = Column(
            col.feature_type, np.zeros((0,) + trailing, dtype=dt),
            None if col.mask is None else np.zeros(0, dtype=bool),
            dict(col.metadata))
    probe = FeatureTable(probe_cols, 0)
    # a leaf span: the stages run their own code over no rows, and what
    # they would say of a table (onehot.*, realvec.*) is not recorded
    with _obs_span("plan.probe", cat=cat, leaf=True, stages=len(stages)):
        for s in stages:
            probe = s.transform(probe)
    for kind, payload in plan.steps:
        if kind != "device":
            continue
        for nm in payload.out_names:
            col = probe[nm]
            payload.out_meta[nm] = (col.feature_type, dict(col.metadata))
            try:
                itemsize = int(np.dtype(
                    getattr(col.values, "dtype", np.float32)).itemsize)
            except TypeError:
                itemsize = 4
            payload.out_shape[nm] = (
                itemsize, tuple(int(x) for x in np.shape(col.values)[1:]))
        for nm in payload.in_names:
            payload.in_shape[nm] = tuple(
                int(x) for x in np.shape(probe[nm].values)[1:])
    return plan


def _schema_fingerprint(stages: List[Any],
                        table: FeatureTable) -> Optional[Tuple]:
    """Per-column (name, dtype, trailing shape, mask-presence) of everything
    the sequence reads from the table: a plan is reusable exactly when this
    matches (row count is free — padding buckets absorb it)."""
    produced = {s.get_output().name for s in stages}
    items: List[Tuple] = []
    seen = set()
    for s in stages:
        for nm in list(_host_inputs(s)) + list(_device_inputs(s)):
            if nm in produced or nm in seen:
                continue
            seen.add(nm)
            col = table.get(nm)
            if col is None:
                # response features are train-only; anything else missing
                # is the eager path's (descriptive) error to raise
                continue
            v = col.values
            items.append((nm, str(getattr(v, "dtype", "object")),
                          tuple(int(x) for x in v.shape[1:]),
                          col.mask is None))
    return tuple(items)


def schema_fingerprint(stages: Sequence[Any],
                       table: FeatureTable) -> List[List[Any]]:
    """Public, JSON-ready view of the plan cache's schema fingerprint:
    ``[[column, dtype, trailing shape, mask-is-None], ...]`` over every
    external column the stage sequence reads from ``table``. Row count is
    deliberately absent (padding buckets absorb it), so a fingerprint
    recorded at save time matches any request batch of the same schema —
    the contract the serving warm-start rides (serving/warmup.py)."""
    fp = _schema_fingerprint(list(stages), table) or ()
    return [[nm, dt, list(shape), bool(maskless)]
            for nm, dt, shape, maskless in fp]


def _record_fallback(site: str, e: BaseException, stages: Sequence[Any],
                     **detail: Any) -> None:
    """The typed ``plan_fallback`` report: a plan that failed to build
    (``plan.compile``) or to run (``plan.execute``) degrades to eager
    dispatch, never silently (docs/plan.md "Fallback semantics")."""
    from .robustness.policy import FaultLog, FaultReport
    FaultLog.record(FaultReport(
        site=site, kind="plan_fallback",
        detail={"error": f"{type(e).__name__}: {e}"[:300], **detail,
                "stages": [getattr(s, "uid", "?") for s in stages]}))


def get_plan(stages: Sequence[Any], table: FeatureTable, *,
             keep_intermediates: bool = True,
             extra_keep: Sequence[str] = (),
             cat: str = "score",
             min_device_stages: int = 1) -> Optional[TransformPlan]:
    """Compile (or fetch from the LRU) the plan for this stage sequence ×
    input schema. Returns None when planning is off, chaos is active, or
    the sequence has fewer than ``min_device_stages`` fusable stages (the
    serve path plans even a single stage — padding + program reuse still
    pay; the per-layer train runs ask for ≥2 so a lone-stage layer skips
    the probe/compile cost fusion cannot repay)."""
    if not planning_applicable():
        return None
    stages = list(stages)
    if sum(1 for s in stages if is_device_capable(s)) < min_device_stages:
        return None
    fp = _schema_fingerprint(stages, table)
    key = (tuple((s.uid, id(s)) for s in stages),
           fp, keep_intermediates, tuple(sorted(extra_keep)))
    if key in _PLAN_CACHE:
        _PLAN_CACHE.move_to_end(key)
        return _PLAN_CACHE[key]
    t0 = time.perf_counter()
    with _obs_span("plan.compile", cat=cat, stages=len(stages)) as sp:
        try:
            plan = _build_plan(stages, table, keep_intermediates,
                               extra_keep, cat)
        except Exception as e:  # infeasible shape → cached eager fallback
            logger.warning("plan compile failed (%s: %s); falling back to "
                           "eager dispatch for this stage sequence",
                           type(e).__name__, e)
            sp.set_attr(failed=f"{type(e).__name__}: {e}"[:200])
            _record_fallback("plan.compile", e, stages)
            plan = None
        if plan is not None:
            sp.set_attr(segments=plan.num_segments,
                        hostStages=plan.num_host_stages)
    if plan is not None:
        # compile ledger: plan (re)builds are classified against the
        # stage sequence's previous build — a cache miss alone says
        # "rebuilt", the ledger says WHY (schema-change with the changed
        # column named, eviction, cold) — docs/observability.md
        plan.ident = "plan/" + ",".join(
            str(getattr(s, "uid", "?")) for s in stages)
        plan.fp_json = [[nm, dt, list(shape), bool(maskless)]
                        for nm, dt, shape, maskless in (fp or ())]
        plan.ident_hash = _ledger.cache_key_hash(
            (plan.ident, plan.fp_json, keep_intermediates,
             tuple(sorted(extra_keep))))
        # AOT program store: a plan whose identity an open store session
        # covers is an assembly step, not a build — its segments will
        # dispatch deserialized programs, so recording a ledger build
        # here would fail the zero-retrace gate for work that was never
        # traced. An active store that does NOT cover it classifies the
        # build aot-miss (programstore/store.py; docs/serving.md).
        from .programstore import store as _pstore
        if _pstore.plan_covered(plan.ident_hash):
            _pstore.record_plan_hit(plan.ident_hash)
        else:
            if _pstore.sessions_active():
                _pstore.note_plan_miss(_ledger.cache_key_hash(key))
            _pstore.offer_plan_ident(plan.ident_hash)
            _ledger.record_build(
                _ledger.current_subsystem("plan"),
                identity=(plan.ident
                          + f"/ki={int(keep_intermediates)}"
                          + f"/ek={','.join(sorted(extra_keep))}"),
                key=_ledger.cache_key_hash(key), fingerprint=plan.fp_json,
                seconds=time.perf_counter() - t0,
                segments=plan.num_segments, cat=cat)
    _PLAN_CACHE[key] = plan
    _PLAN_CACHE.move_to_end(key)
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        evicted_key, _ = _PLAN_CACHE.popitem(last=False)
        _ledger.record_eviction(_ledger.cache_key_hash(evicted_key))
    return plan


def export_plan_programs(plan: TransformPlan,
                         bucket: Optional[int] = None) -> int:
    """Offer every device segment of ``plan`` to the AOT program store
    at ``bucket`` (default the minimum padding bucket — where every warm
    flush of up to 256 rows lands), WITHOUT dispatching anything: the
    traced avals are reconstructed from the zero-row probe's shapes
    (staged inputs are always f32 values padded to the bucket plus a
    bool validity mask — `_run_segment`'s staging contract). This is the
    save-time populate path (``programstore.populate_for_save``) and the
    first-replica fallback when warm dispatches were already traced
    in-process. Returns segments offered; no-op (0) outside a capture
    scope / env store."""
    from .programstore import store as _pstore
    from .utils.padding import _MIN_BUCKET
    if not _pstore.aot_enabled():
        return 0
    import jax
    import jax.numpy as jnp
    n_pad = int(bucket or _MIN_BUCKET)
    offered = 0
    seg_idx = 0
    for kind, seg in plan.steps:
        if kind != "device":
            continue
        if seg.fp_key is None:
            seg.fp_key = _ledger.cache_key_hash(
                (plan.ident, seg_idx, tuple(seg.in_names),
                 tuple(seg.out_names), plan.fp_json))
        vals = tuple(
            jax.ShapeDtypeStruct((n_pad,) + seg.in_shape.get(nm, ()),
                                 jnp.float32)
            for nm in seg.in_names)
        masks = tuple(jax.ShapeDtypeStruct((n_pad,), jnp.bool_)
                      for _ in seg.in_names)
        offered += 1 if _pstore.offer_segment(
            seg.fp_key, n_pad, seg.chain, (vals, masks),
            component="plan-segment",
            identity=f"{plan.ident}/seg{seg_idx}",
            plan_ident=plan.ident_hash) else 0
        seg_idx += 1
    return offered


def _concat_columns(a: Column, b: Column) -> Column:
    """Row-concatenate two halves of a bisected run. Device (jnp) values
    stay on device; host/object arrays concat with numpy. A mask present
    on either half materializes on both (None = all-valid)."""
    va, vb = a.values, b.values
    if isinstance(va, np.ndarray) and isinstance(vb, np.ndarray):
        vals = np.concatenate([va, vb])
    else:
        import jax.numpy as jnp
        vals = jnp.concatenate([jnp.asarray(va), jnp.asarray(vb)])
    if a.mask is None and b.mask is None:
        mask = None
    else:
        mask = np.concatenate([a.valid_mask(), b.valid_mask()])
    return Column(a.feature_type, vals, mask, dict(a.metadata))


def _concat_tables(a: FeatureTable, b: FeatureTable) -> FeatureTable:
    cols = {nm: _concat_columns(a[nm], b[nm]) for nm in a.column_names}
    key = (None if a.key is None or b.key is None
           else np.concatenate([a.key, b.key]))
    return FeatureTable(cols, a.num_rows + b.num_rows, key)


def _execute_adaptive(plan: TransformPlan, table: FeatureTable) -> FeatureTable:
    """Run the plan; on resource exhaustion bisect the row batch into
    smaller padding buckets and concatenate the halves — bit-equal by
    construction (every planned stage is a per-row map; padding rows carry
    zero weight, so a half padded to a smaller bucket produces the exact
    per-row values of the full batch). Below the minimum bucket a further
    bisect cannot shrink the padded program, so the error propagates to
    the existing eager fallback."""
    from .robustness import resources
    from .utils.padding import _MIN_BUCKET
    try:
        return plan.execute(table)
    except Exception as e:
        n = table.num_rows
        if resources.classify_exhaustion(e) is None or n <= _MIN_BUCKET:
            raise
        mid = n // 2
        resources.record_downshift(
            "oom.plan", rows=n, splitRows=[mid, n - mid],
            error=f"{type(e).__name__}: {e}"[:200])
        logger.warning(
            "planned transform run exhausted device memory at %d rows; "
            "bisecting to %d + %d", n, mid, n - mid)
        lo = _execute_adaptive(plan, table.take(np.arange(0, mid)))
        hi = _execute_adaptive(plan, table.take(np.arange(mid, n)))
        return _concat_tables(lo, hi)


def apply_planned(stages: Sequence[Any], table: FeatureTable, *,
                  keep_intermediates: bool = True,
                  extra_keep: Sequence[str] = (),
                  cat: str = "score",
                  min_device_stages: int = 1) -> Optional[FeatureTable]:
    """Run the stage sequence as a compiled plan. Returns the transformed
    table, or None when the caller should dispatch eagerly (planning off /
    chaos active / nothing to fuse / the planned run raised and fell back).

    The fallback contract: a raised planned run records a FaultLog
    ``plan_fallback`` report (+ span event + tg_faults_total counter) and
    returns None; the caller's eager loop then produces identical results —
    plans never transform the input table in place. Resource exhaustion
    gets one extra rung first: the run bisects its row batch into smaller
    padding buckets (``oom_downshift``; docs/robustness.md) and only falls
    back to eager when even the minimum bucket exhausts."""
    plan = get_plan(stages, table, keep_intermediates=keep_intermediates,
                    extra_keep=extra_keep, cat=cat,
                    min_device_stages=min_device_stages)
    if plan is None:
        return None
    try:
        return _execute_adaptive(plan, table)
    except Exception as e:
        _record_fallback("plan.execute", e, stages,
                         segments=plan.num_segments)
        logger.warning(
            "planned transform run failed (%s: %s); falling back to eager "
            "per-stage dispatch for this run", type(e).__name__, e)
        return None
