"""OpWorkflow / OpWorkflowModel — the user-facing engine.

Mirrors the reference workflow layer (reference:
core/src/main/scala/com/salesforce/op/OpWorkflow.scala,
OpWorkflowCore.scala, OpWorkflowModel.scala): the workflow reconstructs the
stage DAG from result-feature lineage, materializes the raw FeatureTable
through a reader, fits the DAG layer-by-layer, and returns a fitted model that
scores (batched, on device) and reports summaries.
"""
from __future__ import annotations

import contextlib
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .dag import (
    apply_transformations_dag, compute_dag, fit_and_transform_dag, validate_dag,
)
from .features import Feature
from .readers.readers import DataFrameReader, Reader, dataframe_to_table
from .stages.base import Estimator, FeatureGeneratorStage
from .table import Column, FeatureTable


def _h2d_bytes() -> Optional[float]:
    """``tg_transfer_bytes_total{direction="h2d"}`` as it stands, for the
    ``h2dBytes`` of a root span; None unless spans and metrics are both
    being recorded (the registry is not touched otherwise)."""
    from .observability import metrics as _obs_metrics
    from .observability.trace import tracing_enabled
    if not (tracing_enabled() and _obs_metrics.metrics_enabled()):
        return None
    return _obs_metrics.registry().counter(
        "tg_transfer_bytes_total", direction="h2d").value


@contextlib.contextmanager
def _root_span(name: str, profiler, **attrs):
    """The outermost span of a train() or score(). With a profiler
    (``with_profiler``) tracing is on for the run and the profiler
    aggregates the run's stage spans when it ends; a traced run with
    metrics on gets ``h2dBytes``, the bytes that went up during it, and
    every traced one ``hbmLiveStart`` / ``hbmLiveEnd``."""
    from .observability.trace import forced_tracing, span
    with forced_tracing() if profiler is not None \
            else contextlib.nullcontext():
        with span(name, hbm=True, **attrs) as root:
            h2d0 = _h2d_bytes()
            yield root
            if h2d0 is not None:
                root.set_attr(h2dBytes=_h2d_bytes() - h2d0)
    if profiler is not None:
        profiler.collect(root)


def _open_run_sentinel(ckpt_dir: Optional[str], resume: bool):
    """Cross-process kill detection (docs/robustness.md): open this run's
    pid+phase sentinel in the checkpoint dir. On ``resume=True``, a stale
    sentinel left by a *different* process is the previous owner's dying
    breath — recorded as a FaultLog ``unclean_exit`` (``oomKillSuspected``
    when its last phase was device work) before this run takes over.
    Returns the started sentinel (cleared by the caller on clean exit),
    or None without a checkpoint dir."""
    if ckpt_dir is None:
        return None
    from .manifest import RunSentinel
    from .robustness.policy import FaultLog, FaultReport
    sentinel = RunSentinel(ckpt_dir)
    if resume:
        stale = sentinel.read_stale()
        if stale is not None:
            detail = {"pid": stale.get("pid"),
                      "phase": stale.get("phase"),
                      "dir": ckpt_dir,
                      "oomKillSuspected":
                          RunSentinel.suspects_oom_kill(stale)}
            FaultLog.record(FaultReport(
                site="manifest.sentinel", kind="unclean_exit",
                detail=dict(detail)))
            # trigger event: the previous owner of this checkpoint dir
            # died mid-run — dump what this process knows (the sentinel's
            # last phase is the dying breath; the resume that follows is
            # the recovery) before training over the evidence
            # (observability/postmortem.py)
            from .observability import postmortem as _postmortem
            _postmortem.trigger("unclean_exit", detail=detail)
    sentinel.start("dag_fit")
    return sentinel


class _WorkflowCore:
    """Shared state between workflow and model (reference OpWorkflowCore.scala:60-84)."""

    def __init__(self):
        self.reader: Optional[Reader] = None
        self.result_features: Tuple[Feature, ...] = ()
        self.raw_features: Tuple[Feature, ...] = ()
        self.blacklisted_features: Tuple[Feature, ...] = ()
        self.parameters: Dict[str, Any] = {}
        self._input_table: Optional[FeatureTable] = None

    # -- input wiring (reference OpWorkflowCore.setInputDataset:146-170) -----
    def set_reader(self, reader: Reader):
        self.reader = reader
        return self

    def set_input_dataset(self, df, key_field: Optional[str] = None):
        self.reader = DataFrameReader(df, key_field=key_field)
        return self

    def set_input_table(self, table: FeatureTable):
        self._input_table = table
        return self

    def set_parameters(self, params: Dict[str, Any]):
        """Workflow-level param injection by stage class name or uid
        (reference OpWorkflow.setStageParameters:166-188)."""
        self.parameters = dict(params)
        return self

    #: OpWorkflow (training) demands response columns too; the fitted model
    #: scores without them (reference: scoring never reads the label)
    _require_response_columns = True

    def _generate_raw_table(self) -> FeatureTable:
        if self._input_table is not None:
            self._validate_input_table(self._input_table)
            return self._input_table
        if self.reader is None:
            raise ValueError(
                "no data source: call set_reader / set_input_dataset / set_input_table")
        return self.reader.generate_table(self.raw_features)

    def _validate_input_table(self, table: FeatureTable) -> None:
        """A user-supplied table bypasses reader-side feature extraction, so
        check it up front: every raw feature needs a column of the matching
        type kind — otherwise a stage fails deep in the DAG with an opaque
        shape/dtype error."""
        required = [f for f in self.raw_features
                    if self._require_response_columns or not f.is_response]
        missing = [f.name for f in required
                   if f.name not in table.column_names]
        if missing:
            raise ValueError(
                f"input table is missing raw feature column(s) {missing}; "
                f"table has {sorted(table.column_names)}")
        mismatched = []
        for f in required:
            col = table[f.name]
            want = f.feature_type.column_kind
            got = col.feature_type.column_kind
            if want != got:
                mismatched.append(f"{f.name}: feature is {f.type_name} "
                                  f"({want}) but column holds "
                                  f"{col.feature_type.__name__} ({got})")
        if mismatched:
            raise ValueError("input table column kind mismatch — "
                             + "; ".join(mismatched))

    def _inject_stage_params(self, stages: Sequence[Any]) -> None:
        per_stage = self.parameters.get("stageParams", {})
        if not per_stage:
            return
        for stage in stages:
            for key in (stage.uid, type(stage).__name__):
                if key in per_stage:
                    stage.set_params(**per_stage[key])


class OpWorkflow(_WorkflowCore):
    """Defines the DAG from result features and trains it
    (reference OpWorkflow.scala:85-444)."""

    def __init__(self):
        super().__init__()
        self._layers = None
        self._raw_feature_filter = None
        self.profiler = None
        self._workflow_cv = False

    def with_workflow_cv(self) -> "OpWorkflow":
        """Leakage-free workflow-level cross-validation: label-dependent prep
        stages (SanityChecker, supervised bucketizers) refit inside every CV
        fold instead of once before the sweep (reference
        OpWorkflow.withWorkflowCV + FitStagesUtil.cutDAG:305-358)."""
        self._workflow_cv = True
        return self

    def with_profiler(self, profiler=None) -> "OpWorkflow":
        """Collect per-stage wall-clock metrics during train (the reference's
        OpSparkListener/logStageMetrics knob, OpParams.scala:66-72): the
        tracer is switched on for the run and the profiler aggregates its
        stage spans, on whichever path (planned or eager) the run takes."""
        from .utils.profiler import StageProfiler
        self.profiler = profiler or StageProfiler()
        return self

    def with_checkpoint_dir(self, path: str) -> "OpWorkflow":
        """Crash-resumable training: every fitted estimator persists to
        ``path`` as it completes, and a re-run skips stages already
        checkpointed there (matched by uid). Writes are atomic (tmp +
        fsync + rename) and committed through a per-directory integrity
        manifest (format version + per-file sha256 + completion records);
        every ModelSelector additionally persists per-candidate sweep
        results as they are evaluated. A re-run (see ``train(resume=True)``)
        restores *verified* stage checkpoints, replays the persisted sweep
        state, and refits only the remainder; corrupt or torn files are
        detected by checksum, reported in ``summary()["faults"]``, and
        never silently used. The TPU build's analog of the reference's
        persist-every-K-stages resilience (OpWorkflowModel.scala:449-455,
        FitStagesUtil.scala:125-131) — deterministic re-execution from
        saved state instead of Spark lineage recomputation."""
        self._checkpoint_dir = path
        return self

    def with_fault_policy(self, policy=None) -> "OpWorkflow":
        """Fault-isolated training: per-stage retries for TRANSIENT errors
        under ``policy`` (a ``robustness.RetryPolicy``; default policy when
        None), on top of the always-on guards (candidate quarantine,
        guarded transfers, checkpoint skip-and-log). Every recovery is
        recorded and surfaced in ``model.summary()["faults"]`` — the TPU
        build's analog of the reference riding ``spark.task.maxFailures`` +
        lineage recomputation (docs/robustness.md)."""
        from .robustness.policy import RetryPolicy
        self._fault_policy = policy or RetryPolicy()
        return self

    def with_mesh(self, mesh) -> "OpWorkflow":
        """Distribute training over a ('data', 'model') device mesh: every
        stage exposing ``set_mesh`` (ModelSelector — rows over 'data',
        configs over 'model') picks it up at train time. The reference's
        cluster topology (Spark driver+executors) becomes a jax mesh; under
        ``jax.distributed`` (parallel.distributed.initialize) the same code
        spans hosts with ICI inside a slice and DCN across slices."""
        self._mesh = mesh
        return self

    def set_result_features(self, *features: Feature) -> "OpWorkflow":
        """Reconstruct the stage DAG from lineage (reference
        OpWorkflow.setResultFeatures:85-105)."""
        if not features:
            raise ValueError("result features cannot be empty")
        self.result_features = tuple(features)
        validate_dag(self.result_features)
        raw: Dict[str, Feature] = {}
        for f in features:
            for r in f.raw_features():
                raw[r.uid] = r
        self.raw_features = tuple(sorted(raw.values(), key=lambda f: f.name))
        self._layers = compute_dag(self.result_features)
        return self

    def with_raw_feature_filter(self, rff) -> "OpWorkflow":
        """Attach a RawFeatureFilter applied before fitting (reference
        OpWorkflow.withRawFeatureFilter:524-563)."""
        self._raw_feature_filter = rff
        return self

    def with_model_stages(self, model: "OpWorkflowModel") -> "OpWorkflow":
        """Partial retrain: swap in already-fitted stages by uid so only new
        estimators refit (reference OpWorkflow.withModelStages:457-461)."""
        if not self.result_features:
            raise ValueError("call set_result_features before with_model_stages")
        fitted = {s.uid: s for s in model.stages}
        self.result_features = tuple(
            f.copy_with_new_stages(fitted) for f in self.result_features)
        self._layers = compute_dag(self.result_features)
        return self

    @property
    def stages(self) -> List[Any]:
        return [s for layer in (self._layers or []) for s, _ in layer]

    def train(self, resume: bool = False, stream=None) -> "OpWorkflowModel":
        """Materialize raw data, fit the DAG, return the fitted model
        (reference OpWorkflow.train:332-357). The whole fit runs under an
        activated FaultLog: retries, quarantines, skipped checkpoints and
        checkpoint restorations recorded anywhere in the stack surface in
        ``summary()["faults"]``.

        ``resume=True`` — preemption recovery: requires
        ``with_checkpoint_dir``; fitted upstream stages restore from
        *verified* checkpoints (manifest + sha256), persisted sweep state
        replays so only unevaluated candidates run, and the returned
        model's ``summary()["resume"]`` records exactly what was restored
        vs refit. Checkpoints failing verification are reported and the
        stage refits — a resume never crashes on (or silently uses) state
        it can deterministically rebuild.

        ``stream=<ChunkSource>`` — out-of-core training
        (docs/streaming.md): the raw table is never materialized; every
        estimator fits as chunked monoid folds over a double-buffered
        host→device feed, per-chunk-checkpointed when a checkpoint dir is
        set, so ``train(resume=True, stream=...)`` after a kill at any
        ``stream.*`` site resumes to a bit-identical model. The fitted
        model is a plain OpWorkflowModel (scoring, serving, persistence
        all unchanged); ``summary()["streaming"]`` carries the feed
        accounting (chunks, uploaded bytes, peak device residency,
        overlap)."""
        from .observability import blackbox as _blackbox
        from .robustness.policy import FaultLog
        fault_log = FaultLog()
        # one flight-recorder correlation id per run: every black-box
        # event recorded inside this train (stream passes, sweep
        # dispatches, fault recoveries) is stamped with it, so a
        # recorder slice replays this run's full timeline
        # (observability/blackbox.py)
        corr = (_blackbox.new_correlation_id("run")
                if _blackbox.blackbox_enabled() else None)
        with fault_log.activate(), _blackbox.correlated(corr), \
                _root_span("workflow.train", self.profiler, cat="train",
                           resume=resume, stream=stream is not None,
                           chips=(int(self._mesh.devices.size) if getattr(
                               self, "_mesh", None) is not None else 1)):
            _blackbox.record("workflow.train", resume=resume,
                             stream=stream is not None)
            if stream is not None:
                model = self._train_streaming(stream, resume=resume)
            else:
                model = self._train_logged(resume=resume)
            _blackbox.record("workflow.train_done")
        model._fault_log = fault_log
        model._correlation = corr
        return model

    def _train_streaming(self, source, resume: bool = False) -> "OpWorkflowModel":
        """Streamed dual of ``_train_logged``: same checkpoint/resume
        machinery, but the DAG fits via ``streaming.fit_dag_streaming``
        (layer-wise chunk folds) instead of one in-memory table. A few
        in-core-only workflow modes are rejected up front with the reason
        rather than silently materializing the dataset."""
        if not self.result_features:
            raise ValueError("call set_result_features before train")
        if self._raw_feature_filter is not None:
            raise ValueError(
                "RawFeatureFilter is not supported with train(stream=...): "
                "its fill-rate/histogram stats are available as streaming "
                "folds (streaming.folds.HistogramFold) but score-vs-train "
                "comparison needs a second stream — train in-core or drop "
                "the filter (ROADMAP item 5)")
        if self._workflow_cv:
            raise ValueError(
                "with_workflow_cv() is not supported with train(stream=...):"
                " per-fold DAG refits need fold-sliced tables")
        if getattr(self, "_mesh", None) is not None:
            raise ValueError(
                "with_mesh() is not supported with train(stream=...) yet: "
                "chunk folds are host monoids (ROADMAP item 3 will shard "
                "chunks over hosts)")
        from .streaming.checkpoint import StreamCheckpoint
        from .streaming.trainer import fit_dag_streaming
        layers = self._layers
        source.bind(self.raw_features)
        self._inject_stage_params([s for layer in layers for s, _ in layer])
        ckpt_dir = getattr(self, "_checkpoint_dir", None)
        if resume and ckpt_dir is None:
            raise ValueError(
                "train(resume=True) requires with_checkpoint_dir(...): "
                "there is no checkpoint state to resume from")
        checkpoint = None
        preloaded = None
        stream_ckpt = None
        if ckpt_dir is not None:
            from .persistence import (load_stage_checkpoints,
                                      open_checkpoint_manifest,
                                      save_stage_checkpoint)
            preloaded = load_stage_checkpoints(ckpt_dir)
            manifest = open_checkpoint_manifest(ckpt_dir)
            checkpoint = lambda model: save_stage_checkpoint(
                model, ckpt_dir, manifest)
            stream_ckpt = StreamCheckpoint(ckpt_dir, manifest,
                                           source.fingerprint())
        # transformed-chunk cache: one handle for the whole train, shared
        # by every stage and pass so repeat sweeps replay prepped chunks
        # (host LRU under TG_STREAM_CACHE_BYTES; sha256-verified disk
        # tier under TG_STREAM_CACHE_DIR — point it at
        # <checkpoint dir>/stream_cache so cached prep survives a kill
        # next to the fold states it matches)
        from .streaming.cache import ChunkCache
        stream_cache = ChunkCache.from_env()
        from .manifest import active_sentinel
        sentinel = _open_run_sentinel(ckpt_dir, resume)
        with active_sentinel(sentinel):
            fitted, transformers, stats = fit_dag_streaming(
                source, layers,
                checkpoint=checkpoint, stream_checkpoint=stream_ckpt,
                preloaded=preloaded,
                retry_policy=getattr(self, "_fault_policy", None),
                cache=stream_cache)
        if sentinel is not None:
            sentinel.clear()
        new_results = tuple(
            f.copy_with_new_stages(fitted) for f in self.result_features)
        model = OpWorkflowModel()
        model.reader = self.reader
        model.parameters = self.parameters
        model.result_features = new_results
        model.raw_features = self.raw_features
        model.blacklisted_features = ()
        model.rff_results = None
        # a small transformed head-of-stream probe stands in for the full
        # train table: it carries the fitted schema (vector widths,
        # metadata) that model persistence / serve warm-start fingerprint
        # read — O(probe rows), never the dataset
        probe = next(iter(source.chunks(0))).table
        if probe.num_rows > 256:
            probe = probe.take(np.arange(256))
        for m in transformers:
            probe = m.transform(probe)
        model.train_table = probe
        model._stream_stats = stats
        model._stream_cache_stats = (stream_cache.stats
                                     if stream_cache is not None else None)
        model._fitted_stage_uids = sorted(fitted)
        model._resume_requested = resume
        model._layers = compute_dag(new_results)
        return model

    def _train_logged(self, resume: bool = False) -> "OpWorkflowModel":
        if not self.result_features:
            raise ValueError("call set_result_features before train")
        table = self._generate_raw_table()
        layers = self._layers
        result_features = self.result_features
        blacklisted: Tuple[Feature, ...] = ()
        rff_results = None
        if self._raw_feature_filter is not None:
            if (getattr(self, "_mesh", None) is not None
                    and hasattr(self._raw_feature_filter, "set_mesh")):
                # RFF is the first full pass over raw data — shard it too
                self._raw_feature_filter.set_mesh(self._mesh)
            table, blacklist, rff_results = self._raw_feature_filter.filter_raw(
                table, self.raw_features)
            if blacklist:
                result_features, layers = self._apply_blacklist(blacklist)
                blacklisted = tuple(blacklist)
        self._inject_stage_params([s for layer in layers for s, _ in layer])
        mesh = getattr(self, "_mesh", None)
        if mesh is not None:
            for layer in layers:
                for s, _ in layer:
                    if hasattr(s, "set_mesh"):
                        s.set_mesh(mesh)
        ckpt_dir = getattr(self, "_checkpoint_dir", None)
        if resume and ckpt_dir is None:
            raise ValueError(
                "train(resume=True) requires with_checkpoint_dir(...): "
                "there is no checkpoint state to resume from")
        checkpoint = None
        preloaded = None
        if ckpt_dir is not None:
            from .impl.tuning.sweep_checkpoint import SweepCheckpoint
            from .persistence import (load_stage_checkpoints,
                                      open_checkpoint_manifest,
                                      save_stage_checkpoint)
            # restored stages are manifest-verified (sha256); failures are
            # reported as checkpoint_skipped and the stage refits
            preloaded = load_stage_checkpoints(ckpt_dir)
            # ONE manifest object shared by stage checkpoints and sweep
            # state, so sequential commits never clobber each other
            manifest = open_checkpoint_manifest(ckpt_dir)
            checkpoint = lambda model: save_stage_checkpoint(
                model, ckpt_dir, manifest)
            for layer in layers:
                for s, _ in layer:
                    if hasattr(s, "set_sweep_checkpoint"):
                        s.set_sweep_checkpoint(
                            SweepCheckpoint(ckpt_dir, s.uid, manifest))
        retry_policy = getattr(self, "_fault_policy", None)
        from .manifest import active_sentinel
        sentinel = _open_run_sentinel(ckpt_dir, resume)
        with active_sentinel(sentinel):
            if self._workflow_cv:
                table, fitted = self._fit_with_workflow_cv(table, layers)
            else:
                table, fitted = fit_and_transform_dag(
                    table, layers, checkpoint=checkpoint, preloaded=preloaded,
                    retry_policy=retry_policy)
        if sentinel is not None:
            # clean-exit commit: a kill anywhere above leaves the sentinel
            # for the next resume to report
            sentinel.clear()
        new_results = tuple(
            f.copy_with_new_stages(fitted) for f in result_features)
        model = OpWorkflowModel()
        model.reader = self.reader
        model.parameters = self.parameters
        model.result_features = new_results
        model.raw_features = self.raw_features
        model.blacklisted_features = blacklisted
        model.rff_results = rff_results
        model.train_table = table
        #: resume accounting: which estimator uids this train fitted (or
        #: restored) — summary()["resume"] splits them via the fault log
        model._fitted_stage_uids = sorted(fitted)
        model._resume_requested = resume
        if self.profiler is not None:
            # score timings get their own collector — mixing them into the
            # train AppMetrics would conflate fit and serve costs
            from .utils.profiler import StageProfiler
            model.profiler = StageProfiler()
        model._layers = compute_dag(new_results)
        return model

    def drift_refit_hook(self, save_dir: str, resume: Optional[bool] = None):
        """A serving-registry refit hook bound to this workflow
        (``ModelRegistry(refit_hook=...)`` / ``set_refit_hook``;
        docs/serving.md "Drift monitoring & self-healing"): when a served
        model's drift verdict degrades, the registry calls the hook on a
        background thread; it retrains this workflow on whatever its
        reader/input currently yields (point the reader at fresh data —
        that is the whole point of a drift refit), saves the result under
        ``save_dir`` (``refit_000001``, ``refit_000002``, ... so the
        in-service model directory is never written over while being
        read), and returns the saved path for the registry's
        manifest-verified load + warm hot swap.

        ``resume`` defaults to whether a checkpoint dir is attached —
        ``with_checkpoint_dir`` makes the refit itself preemption-safe
        (``train(resume=True)`` restores verified stages and replays
        sweep state instead of starting over after a kill)."""
        import os as _os
        counter = {"n": 0}
        if resume is None:
            resume = getattr(self, "_checkpoint_dir", None) is not None

        def hook(name: str, runtime, report) -> str:
            counter["n"] += 1
            model = self.train(resume=resume)
            path = _os.path.join(save_dir, f"refit_{counter['n']:06d}")
            model.save(path)
            return path

        return hook

    def _fit_with_workflow_cv(self, table: FeatureTable, layers):
        """The cutDAG path (reference FitStagesUtil.cutDAG:305-358 +
        OpWorkflow.fitStages:397-442): fit label-independent stages once,
        run ModelSelector.find_best_estimator with per-fold copies of the
        label-dependent ("during") DAG, then fit everything remaining —
        including the during stages on the full data and the selector, which
        now skips its own sweep and refits the recorded winner."""
        from .impl.selector.model_selector import ModelSelector
        from .stages.base import AllowLabelAsInput

        all_stages = [(s, d) for layer in layers for s, d in layer]
        selectors = [s for s, _ in all_stages if isinstance(s, ModelSelector)]
        if len(selectors) != 1:
            raise ValueError(
                f"workflow-level CV requires exactly one ModelSelector, "
                f"found {len(selectors)} (reference FitStagesUtil.cutDAG:313)")
        sel = selectors[0]
        _, vec_f = sel.input_features

        # taint propagation over the FULL result ancestry: a feature is
        # label-dependent if its origin stage consumes the label while
        # producing a predictor (AllowLabelAsInput estimators), is the
        # selector itself, or has any tainted parent. Tainted stages — and
        # everything downstream of them, selector outputs included — defer to
        # the rest phase so their inputs exist when they fit.
        tainted: Dict[str, bool] = {}
        ordered: List[Feature] = []
        seen: set = set()
        for rf in self.result_features:
            for feat in rf.all_features():      # post-order: parents first
                if feat.uid in seen:
                    continue
                seen.add(feat.uid)
                ordered.append(feat)
                st = feat.origin_stage
                own = ((isinstance(st, Estimator)
                        and isinstance(st, AllowLabelAsInput))
                       or st is sel)
                tainted[feat.uid] = own or any(tainted.get(p.uid, False)
                                               for p in feat.parents)
        tainted_stage_uids = {f_.origin_stage.uid for f_ in ordered
                              if tainted[f_.uid] and not f_.is_raw}

        retry_policy = getattr(self, "_fault_policy", None)
        before_layers = [[(s, d) for s, d in layer
                          if s.uid not in tainted_stage_uids]
                         for layer in layers]
        table1, fitted_before = fit_and_transform_dag(
            table, before_layers, retry_policy=retry_policy)

        # the in-CV DAG refit per fold: tainted estimator stages on the
        # selector-input ancestry (not the selector, not its downstream)
        vec_anc = {f_.origin_stage.uid for f_ in vec_f.all_features()
                   if not f_.is_raw}
        during_layers = [[(s, d) for s, d in layer
                          if s.uid in tainted_stage_uids and s.uid in vec_anc
                          and s is not sel]
                         for layer in layers]
        during_layers = [l for l in during_layers if l]

        rest_layers = [[(s, d) for s, d in layer
                        if s.uid in tainted_stage_uids]
                       for layer in layers]
        rest_layers = [l for l in rest_layers if l]
        try:
            sel.find_best_estimator(table1, during_layers)
            table2, fitted_rest = fit_and_transform_dag(
                table1, rest_layers, retry_policy=retry_policy)
        except Exception:
            # don't leave a recorded winner behind: a later plain train()
            # on the same stage objects must validate from scratch, not
            # silently reuse a selection made on this failed run's data
            sel._preset_best = None
            raise
        return table2, {**fitted_before, **fitted_rest}

    def _apply_blacklist(self, blacklist: Sequence[Feature]):
        """DAG surgery removing blacklisted raw features (reference
        OpWorkflow.setBlacklist:112-154). Stages whose inputs are all
        blacklisted are dropped; vectorizers drop the blacklisted inputs."""
        gone = {f.uid for f in blacklist}

        def rebuild(f: Feature, cache: Dict[str, Optional[Feature]]) -> Optional[Feature]:
            if f.uid in cache:
                return cache[f.uid]
            if f.is_raw:
                out = None if f.uid in gone else f
                cache[f.uid] = out
                return out
            kept_parents = []
            for p in f.parents:
                np_ = rebuild(p, cache)
                if np_ is not None:
                    kept_parents.append(np_)
            if not kept_parents:
                cache[f.uid] = None
                return None
            stage = f.origin_stage
            if len(kept_parents) != len(f.parents):
                import copy as _copy
                stage = _copy.copy(stage)
                stage.input_features = tuple(kept_parents)
                stage._output_feature = None
                out = stage.get_output()
                # keep original identity so downstream wiring still matches
                out.name = f.name
                out.uid = f.uid
                stage._output_feature = out
            else:
                stage.input_features = tuple(kept_parents)
                out = Feature(f.name, f.feature_type, f.is_response, stage,
                              kept_parents, uid=f.uid)
                stage._output_feature = out
            cache[f.uid] = out
            return out

        cache: Dict[str, Optional[Feature]] = {}
        new_results = []
        for f in self.result_features:
            nf = rebuild(f, cache)
            if nf is None:
                raise ValueError(
                    f"result feature '{f.name}' lost all inputs to the raw feature filter")
            new_results.append(nf)
        return tuple(new_results), compute_dag(new_results)


class OpWorkflowModel(_WorkflowCore):
    """Fitted workflow (reference OpWorkflowModel.scala)."""

    #: serve-time tables may omit the label column — scoring never reads it
    _require_response_columns = False

    def __init__(self):
        super().__init__()
        self._layers = None
        self.train_table: Optional[FeatureTable] = None
        self.rff_results = None
        self.profiler = None
        #: train-scoped fault accounting (robustness.FaultLog); None for
        #: models loaded from disk — wiring state, never serialized
        self._fault_log = None

    @property
    def stages(self) -> List[Any]:
        return [s for layer in (self._layers or []) for s, _ in layer]

    def get_stage(self, uid: str) -> Any:
        for s in self.stages:
            if s.uid == uid:
                return s
        raise KeyError(uid)

    # -- scoring (reference OpWorkflowModel.score:254-324) -------------------
    def score(self, table: Optional[FeatureTable] = None, df=None,
              keep_raw_features: bool = True,
              keep_intermediate_features: bool = True) -> FeatureTable:
        """Batch scoring over the fitted transformer DAG. The pass runs on
        the fused substrate: the transform-plan compiler (``plan.py``)
        traces each device-fusable segment into one XLA program (eager
        per-stage dispatch under ``plan.enable_planning(False)`` or active
        chaos — results are bit-identical either way, docs/plan.md)."""
        if df is not None:
            table = dataframe_to_table(df, self.raw_features)
        if table is None:
            table = self._generate_raw_table()
        with _root_span("workflow.score", self.profiler, cat="score",
                        rows=table.num_rows):
            scored = apply_transformations_dag(table, self._layers)
        if keep_raw_features and keep_intermediate_features:
            return scored
        keep = [f.name for f in self.result_features if f.name in scored.column_names]
        if keep_raw_features:
            keep = [f.name for f in self.raw_features] + keep
        return scored.select(keep)

    def score_and_evaluate(self, evaluator, table: Optional[FeatureTable] = None,
                           df=None) -> Tuple[FeatureTable, Dict[str, float]]:
        scored = self.score(table=table, df=df)
        return scored, evaluator.evaluate_all(scored)

    def evaluate(self, evaluator, table: Optional[FeatureTable] = None) -> Dict[str, float]:
        return self.score_and_evaluate(evaluator, table=table)[1]

    # -- persistence (reference OpWorkflowModel.save) ------------------------
    def save(self, path: str) -> None:
        from .persistence import save_model
        save_model(self, path)

    @staticmethod
    def load(path: str, workflow: Optional["OpWorkflow"] = None) -> "OpWorkflowModel":
        from .persistence import load_model
        return load_model(path, workflow=workflow)

    # -- local scoring (reference local/OpWorkflowModelLocal.scala) ----------
    def score_function(self):
        from .local import score_function
        return score_function(self)

    # -- summaries (reference OpWorkflowModel.summary:183-211) ---------------
    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for stage in self.stages:
            md = getattr(stage, "summary_metadata", None)
            if md:
                out[stage.uid] = md
        # fault accounting for THIS train run: quarantined candidates,
        # successful retries, skipped checkpoints, restorations
        # (docs/robustness.md; empty sections for models loaded from disk —
        # the log is train-scoped)
        from .robustness.policy import FaultLog
        log = getattr(self, "_fault_log", None)
        out["faults"] = (log or FaultLog()).to_json()
        # resume accounting: what this train restored from verified
        # checkpoints vs actually (re)fit (docs/robustness.md "Resume
        # semantics"). Empty/false for models loaded from disk.
        restored_stages = sorted(
            r.detail.get("uid") for r in (log.reports if log else [])
            if r.kind == "restored" and r.site == "dag.stage_fit")
        out["resume"] = {
            "requested": bool(getattr(self, "_resume_requested", False)),
            "restoredStages": restored_stages,
            "refitStages": [
                uid for uid in getattr(self, "_fitted_stage_uids", [])
                if uid not in set(restored_stages)],
            "restoredSweepCandidates": [
                dict(r.detail) for r in (log.reports if log else [])
                if r.kind == "restored" and r.site == "sweep.candidate"],
        }
        # live telemetry aggregates (docs/observability.md): per-stage /
        # per-family span timings, fault counters, scoring latency
        # quantiles, compile-cache hit/miss. Process-scoped (the tracer and
        # registry outlive any one train — exactly like serving counters
        # should); {"enabled": {... false}} sections when observability is
        # off.
        from .observability import summarize
        out["observability"] = summarize()
        # out-of-core feed accounting for streamed trains (chunks, uploaded
        # bytes, peak device residency, overlap — docs/streaming.md);
        # absent for in-core/loaded models
        stream_stats = getattr(self, "_stream_stats", None)
        if stream_stats is not None:
            out["streaming"] = stream_stats.to_json()
            cache_stats = getattr(self, "_stream_cache_stats", None)
            if cache_stats is not None:
                out["streaming"]["cache"] = cache_stats.to_json()
        return out

    def summary_json(self) -> str:
        return json.dumps(self.summary(), indent=2, default=_json_default)

    def summary_pretty(self) -> str:
        lines: List[str] = ["Workflow summary:"]
        for stage in self.stages:
            pretty = getattr(stage, "summary_pretty", None)
            if callable(pretty):
                lines.append(pretty())
            elif getattr(stage, "summary_metadata", None):
                lines.append(f"-- {type(stage).__name__} ({stage.uid})")
        return "\n".join(lines)

    def model_insights(self, feature=None):
        """Full model report extracted from the fitted stages (reference
        OpWorkflowModel.modelInsights:163-176). ``feature`` is accepted for
        API parity; insights always cover the model's result features."""
        from .insights import ModelInsights
        return ModelInsights.extract(self)


def _json_default(o):
    if isinstance(o, (np.floating, np.integer)):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    if hasattr(o, "to_json"):
        return o.to_json()
    if hasattr(o, "__dict__"):
        return {k: v for k, v in vars(o).items() if not k.startswith("_")}
    return str(o)
