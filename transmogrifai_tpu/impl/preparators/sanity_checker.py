"""SanityChecker — automated feature validation.

TPU re-design of the reference SanityChecker
(reference: core/.../impl/preparators/SanityChecker.scala — sampling :524-529 &
limits :720-739, colStats :574-576, correlations :634-638, categorical
association stats categoricalTests :420-516, removal reasons
ColumnStatistics.reasonsToRemove :783-832, index-keep model transformFn
:707-717, summary metadata :678).

Everything numeric happens in a handful of jitted kernels over the feature
matrix: one fused stats pass (count/mean/var/min/max), one correlation kernel
(Pearson or Spearman vs label), and one MXU matmul per categorical group for
contingency tables — replacing Spark's colStats/corr/reduceByKey jobs.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from ...observability.trace import span as _obs_span
from ...ops.stats import (
    col_stats, contingency_stats, contingency_table, pearson_correlation,
    pearson_correlation_matrix, spearman_correlation,
)
from ...stages.base import (AllowLabelAsInput, Estimator, PendingFit,
                            Transformer, fetch_pending)
from ...table import Column, FeatureTable
from ...types import OPVector, RealNN
from ...utils.padding import pad_rows, padded_valid_mask
from ...vector_metadata import VectorColumnMetadata, VectorMetadata
from .sanity_checker_metadata import (
    CategoricalGroupStats, ColumnStatistics, SanityCheckerSummary,
)

#: feature types whose shared-hash slots protect_text_shared_hash exempts
_TEXT_PARENT_TYPES = ("Text", "TextArea", "TextMap", "TextAreaMap",
                      "TextList")  # .tf()/HashingVectorizer slots


def _is_text_shared_hash(c: VectorColumnMetadata) -> bool:
    """Shared-hash text slot (reference SanityChecker.isTextSharedHash :840:
    text-derived, not an indicator). In this codebase's metadata convention
    hashed slots carry ``descriptor_value='hash_<j>'`` (and keep their
    grouping so null-indicator siblings share the feature group), so the
    test is: text parent, hash descriptor, no indicator value."""
    return (c.parent_feature_type in _TEXT_PARENT_TYPES
            and c.indicator_value is None
            and (c.descriptor_value or "").startswith("hash_"))


def _contingency_stats_np(t: np.ndarray) -> Dict[str, Any]:
    """Association stats on a small (m, L) contingency table, host-side
    (same math as ops.stats.contingency_stats — the tables are tiny, so
    numpy beats a device dispatch per group). Includes mutual information
    and per-cell pointwise mutual information (reference
    OpStatistics.contingencyStats:300)."""
    t = t.astype(np.float64)
    n = max(t.sum(), 1.0)
    row = t.sum(axis=1)
    col = t.sum(axis=0)
    expected = row[:, None] * col[None, :] / n
    chi2 = np.where(expected > 0,
                    (t - expected) ** 2 / np.maximum(expected, 1e-30),
                    0.0).sum()
    min_dim = max(min((row > 0).sum(), (col > 0).sum()) - 1, 1)
    conf = np.where(row[:, None] > 0,
                    t / np.maximum(row[:, None], 1e-30), 0.0)
    p = t / n
    denom = (row[:, None] / n) * (col[None, :] / n)
    pmi = np.where((p > 0) & (denom > 0),
                   np.log2(np.maximum(p, 1e-300)
                           / np.maximum(denom, 1e-300)), 0.0)
    return {
        "cramers_v": float(np.sqrt(chi2 / (n * min_dim))),
        "max_rule_confidence": conf.max(axis=1),
        "support": row / n,
        "mutual_info": float((p * pmi).sum()),
        "pointwise_mutual_info": pmi,
    }


class SanityCheckerDefaults:
    """(reference SanityCheckerParams defaults :59-226, object SanityChecker
    :720-739 — ProtectTextSharedHash=False matches the reference object
    default; round 1 of this build had it True, undocumented). One
    deliberate deviation: RemoveBadFeatures defaults True here (False in
    the reference object, but every reference example/selector flow turns
    it on — removal is the stage's purpose in this framework's default
    pipelines)."""
    CheckSample = 1.0
    SampleLowerLimit = 1_000
    SampleUpperLimit = 1_000_000
    MaxCorrelation = 0.95
    MinCorrelation = 0.0
    MaxCramersV = 0.95
    MinVariance = 1e-5
    MinRequiredRuleSupport = 1.0
    MaxRuleConfidence = 1.0
    RemoveFeatureGroup = True
    ProtectTextSharedHash = False
    RemoveBadFeatures = True
    CorrelationTypeSpearman = False


class SanityChecker(AllowLabelAsInput, Estimator):
    """BinaryEstimator[RealNN, OPVector] → OPVector: drops features whose
    statistics flag leakage or uselessness."""

    input_types = (RealNN, OPVector)
    output_type = OPVector

    def __init__(self,
                 check_sample: float = SanityCheckerDefaults.CheckSample,
                 sample_lower_limit: int = SanityCheckerDefaults.SampleLowerLimit,
                 sample_upper_limit: int = SanityCheckerDefaults.SampleUpperLimit,
                 protect_text_shared_hash: bool = SanityCheckerDefaults.ProtectTextSharedHash,
                 max_correlation: float = SanityCheckerDefaults.MaxCorrelation,
                 min_correlation: float = SanityCheckerDefaults.MinCorrelation,
                 max_cramers_v: float = SanityCheckerDefaults.MaxCramersV,
                 min_variance: float = SanityCheckerDefaults.MinVariance,
                 max_rule_confidence: float = SanityCheckerDefaults.MaxRuleConfidence,
                 min_required_rule_support: float = SanityCheckerDefaults.MinRequiredRuleSupport,
                 remove_bad_features: bool = SanityCheckerDefaults.RemoveBadFeatures,
                 remove_feature_group: bool = SanityCheckerDefaults.RemoveFeatureGroup,
                 correlation_type_spearman: bool = SanityCheckerDefaults.CorrelationTypeSpearman,
                 correlations: str = "label",
                 seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__("sanityCheck", uid)
        self.check_sample = check_sample
        self.sample_lower_limit = sample_lower_limit
        self.sample_upper_limit = sample_upper_limit
        self.protect_text_shared_hash = protect_text_shared_hash
        self.max_correlation = max_correlation
        self.min_correlation = min_correlation
        self.max_cramers_v = max_cramers_v
        self.min_variance = min_variance
        self.max_rule_confidence = max_rule_confidence
        self.min_required_rule_support = min_required_rule_support
        self.remove_bad_features = remove_bad_features
        self.remove_feature_group = remove_feature_group
        self.correlation_type_spearman = correlation_type_spearman
        if correlations not in ("label", "full"):
            raise ValueError(
                f"correlations must be 'label' or 'full', got {correlations!r}")
        #: "label" computes only label-vs-feature correlations; "full" also
        #: records the (d, d) feature-feature matrix in the summary
        #: (reference SanityChecker.scala:634-638 featureLabelCorrOnly)
        self.correlations = correlations
        self.seed = seed
        self.mesh = None

    def set_mesh(self, mesh) -> "SanityChecker":
        """Run the stats pass (colStats + correlations + contingency counts)
        over rows sharded on the mesh's 'data' axis — the TPU-native analog
        of the reference's distributed colStats/reduceByKey
        (SanityChecker.scala:574-576, :433-440). XLA inserts the psum
        collectives; pad rows carry mask=False."""
        self.mesh = mesh
        return self

    # -- fit ------------------------------------------------------------------
    def fit(self, table: FeatureTable) -> Transformer:
        pending = self.fit_queued(table)
        # the one fetch the fit blocks on: the host waits here for every
        # program fit_queued launched (and for what they wait for). Even a
        # single fit goes through the fused per-dtype transfer: a plain
        # np.asarray per leaf is one blocking device->host sync EACH
        with _obs_span("sanity.collect", leaves=len(pending.dev)):
            (host,) = fetch_pending([pending])
        return pending._finish(host)

    def fit_queued(self, table: FeatureTable) -> PendingFit:
        """Queued-fit protocol (stages/base.py): dispatch every device stat
        program (col stats, label correlation, optional full matrix,
        contingency counts) and defer the single host transfer + column
        decisions to finish — workflow-level CV queues all F folds' checker
        fits before one sync (reference OpValidator.applyDAG :228-256 runs
        fold DAG copies on concurrent Futures)."""
        label_f, vec_f = self.input_features
        mesh = getattr(self, "mesh", None)
        with _obs_span("sanity.sample") as step:
            y = np.asarray(table[label_f.name].values,
                           dtype=np.float32).reshape(-1)
            col = table[vec_f.name]
            vm: Optional[VectorMetadata] = col.metadata.get("vector_meta")
            # the feature matrix stays on device end to end — at millions of
            # rows a host round-trip would dwarf the stats kernels themselves
            Xd_all = jnp.asarray(col.values, dtype=jnp.float32)
            n, d = Xd_all.shape

            # sampling (reference fraction :524-529: the requested
            # check_sample fraction is clamped so the sample never goes
            # below sample_lower_limit rows nor above sample_upper_limit)
            min_frac = min(1.0, self.sample_lower_limit / max(n, 1))
            max_frac = max(0.0, self.sample_upper_limit / max(n, 1))
            frac = max(min(self.check_sample, max_frac), min_frac)
            target = min(int(round(n * frac)), n)
            n_data = mesh.shape["data"] if mesh is not None else 1
            row_mask = None
            idx = None
            if target < n:
                idx = np.random.RandomState(self.seed).choice(
                    n, size=target, replace=False)
            ys = y if idx is None else y[idx]
            if idx is not None and mesh is not None and n % n_data == 0:
                # the sample of a table sharded over the mesh: gathered
                # shard to shard (no chip holds the sample whole), padded to
                # the data axis with row 0 under a False mask
                from ...parallel.sharded import place_rows, take_rows
                n_s = -(-target // n_data) * n_data
                Xd = take_rows(Xd_all, pad_rows(idx, n_s), mesh,
                               site="sanity.sample")
                yd = place_rows(pad_rows(ys, n_s), mesh,
                                site="checker.upload")
                row_mask = place_rows(padded_valid_mask(None, target, n_s),
                                      mesh, site="checker.upload")
            else:
                Xd = Xd_all if idx is None else Xd_all[jnp.asarray(idx)]
                yd = jnp.asarray(ys)
                if mesh is not None:
                    from ...parallel.sharded import shard_rows
                    Xd, row_mask, _ = shard_rows(Xd, None, mesh)
                    yd, _, _ = shard_rows(yd, None, mesh)
            step.set_attr(rows=n, sampleRows=target)
        if mesh is not None:
            self._stats_input_sharding = str(Xd.sharding)
        with _obs_span("sanity.stats", features=d):
            dev, groups = self._launch_stats(Xd, yd, ys, row_mask, vm)
        n_sample = int(len(ys))
        sharding_note = getattr(self, "_stats_input_sharding", None)

        def finish(host: Dict[str, np.ndarray]) -> Transformer:
            return self._finish_from_host(host, d=d, vm=vm, groups=groups,
                                          n_sample=n_sample,
                                          sharding_note=sharding_note)

        return PendingFit(dev, finish)

    def _launch_stats(self, Xd, yd, ys: np.ndarray, row_mask,
                      vm: Optional[VectorMetadata]
                      ) -> Tuple[Dict[str, Any], List[Any]]:
        """Launch the stat programs over the sample: (name -> device array,
        the indicator groups whose contingency counts are among them)."""
        stats = col_stats(Xd, row_mask)
        if self.correlation_type_spearman:
            corr = spearman_correlation(Xd, yd, row_mask)
        else:
            corr = pearson_correlation(Xd, yd, row_mask)
        dev: Dict[str, Any] = dict(stats._asdict())
        dev["corr"] = corr
        if getattr(self, "correlations", "label") == "full":
            # (d, d) feature-feature matrix on device (one MXU matmul);
            # Spearman mode ranks the columns first, matching the label path
            Xc = Xd
            if self.correlation_type_spearman:
                import jax as _jax
                from ...ops.stats import _rank
                Xc = _jax.vmap(_rank, in_axes=1, out_axes=1)(Xd)
            dev["feature_corr"] = pearson_correlation_matrix(Xc, row_mask)

        # categorical association stats per feature group (reference
        # :420-516): dispatch the one contingency matmul for every
        # indicator column now; the per-group association stats run on the
        # tiny (m, L) numpy tables at finish time
        groups: List[Any] = []
        if vm is not None:
            labels = np.unique(ys)
            is_binary_like = (len(labels) <= 20
                              and np.allclose(labels, labels.astype(int)))
            if is_binary_like:
                # yd is the (possibly mesh-padded) device label vector; pad
                # rows are excluded via row_mask in the contingency matmul
                label_idx = yd.astype(jnp.int32)
                num_labels = int(ys.max()) + 1
                # only indicator (0/1 pivot) groups get contingency stats
                groups = [(g, idxs) for g, idxs in vm.index_of_group().items()
                          if all(vm.columns[i].indicator_value is not None
                                 for i in idxs)]
                if groups:
                    all_idx = np.concatenate(
                        [np.asarray(idxs) for _, idxs in groups])
                    dev["counts"] = contingency_table(
                        Xd[:, jnp.asarray(all_idx)], label_idx, num_labels,
                        row_mask)
        return dev, groups

    # -- streaming fit (OpWorkflow.train(stream=...), docs/streaming.md) -----
    def fit_streaming_prep(self, run):
        """Single-pass prep spec ``(pass_id, fold, extract, finish)`` for
        the trainer's fused layer sweep (streaming/trainer.py) — the
        sanity stats were already one composite pass, so the spec just
        exposes its pieces."""
        from ...streaming.folds import (
            ColStatsFold, CompositeFold, ContingencyFold, CorrelationFold,
        )
        if self.correlation_type_spearman:
            raise ValueError(
                "SanityChecker(correlation_type_spearman=True) cannot fit "
                "on a stream: exact ranks need the full dataset. Use "
                "Pearson, or train in-core.")
        label_f, vec_f = self.input_features
        probe = run.probe_table()
        col = probe[vec_f.name]
        vm: Optional[VectorMetadata] = col.metadata.get("vector_meta")
        d = col.width

        groups: List[Any] = []
        all_idx = np.zeros(0, np.int64)
        if vm is not None:
            groups = [(g, idxs) for g, idxs in vm.index_of_group().items()
                      if all(vm.columns[i].indicator_value is not None
                             for i in idxs)]
            if groups:
                all_idx = np.concatenate(
                    [np.asarray(idxs) for _, idxs in groups])
        folds: Dict[str, Any] = {
            "stats": ColStatsFold(d),
            "corr": CorrelationFold(
                d, full=getattr(self, "correlations", "label") == "full"),
        }
        if groups:
            folds["cont"] = ContingencyFold(len(all_idx))
        composite = CompositeFold(folds)

        def extract(table: FeatureTable):
            X = np.asarray(table[vec_f.name].values, dtype=np.float32)
            y = np.asarray(table[label_f.name].values,
                           dtype=np.float32).reshape(-1)
            parts = {"stats": (X,), "corr": (X, y)}
            if groups:
                parts["cont"] = (X[:, all_idx], y)
            return (parts,)

        def finish(state) -> Transformer:
            grps = groups
            res = composite.finalize(state)
            stats = res["stats"]
            host: Dict[str, np.ndarray] = {
                "count": stats.count, "mean": stats.mean,
                "variance": stats.variance, "min": stats.min,
                "max": stats.max, "corr": res["corr"],
            }
            if folds["corr"].full:
                host["feature_corr"] = folds["corr"].finalize_matrix(
                    state["corr"])
            n_sample = int(state["corr"]["n"])
            if grps:
                counts = res["cont"]
                if counts is None:
                    # labels were not binary-like: same branch as in-core
                    grps = []
                else:
                    host["counts"] = counts.astype(np.float64)
            return self._finish_from_host(host, d=d, vm=vm, groups=grps,
                                          n_sample=n_sample)

        return "sanity", composite, extract, finish

    def fit_streaming(self, run) -> Transformer:
        """One chunked pass of monoid folds — the out-of-core dual of the
        device stats pass: col moments, label correlations (co-moment
        merge), optional full correlation matrix, and contingency counts
        all accumulate in exact-f64 host folds and feed the SAME
        ``_finish_from_host`` decision logic the in-core fit uses. Two
        documented deviations: no sampling (the stream folds every row —
        ``check_sample``/limits describe the in-core reservoir) and no
        Spearman (exact streaming ranks need a sort over the full
        dataset)."""
        pass_id, fold, extract, finish = self.fit_streaming_prep(run)
        return finish(run.fold(pass_id, fold, extract))

    def _finish_from_host(self, host: Dict[str, np.ndarray], *, d: int,
                          vm: Optional[VectorMetadata], groups: List[Any],
                          n_sample: int,
                          sharding_note: Optional[str] = None) -> Transformer:
        """Column decisions from the materialized stat arrays — shared by
        the device fit (``fit_queued``) and the streaming fold fit
        (``fit_streaming``): both paths hand the identical host dict
        (count/mean/variance/min/max, corr, optional feature_corr, stacked
        contingency counts) to the identical removal logic."""
        with _obs_span("sanity.decide", features=d) as step:
            model = self._decide(host, d=d, vm=vm, groups=groups,
                                 n_sample=n_sample,
                                 sharding_note=sharding_note)
            step.set_attr(dropped=d - len(model.keep_indices))
        return model

    def _decide(self, host: Dict[str, np.ndarray], *, d: int,
                vm: Optional[VectorMetadata], groups: List[Any],
                n_sample: int, sharding_note: Optional[str]) -> Transformer:
        """The host's rules over the stat arrays: the fitted model."""
        stats = {k: host[k]
                 for k in ("count", "mean", "variance", "min", "max")}
        corr = host["corr"]
        feature_corr = host.get("feature_corr")
        cramers_by_col = np.full(d, np.nan)
        rule_conf_by_col = np.full(d, np.nan)
        support_by_col = np.full(d, np.nan)
        group_cramers: Dict[str, float] = {}
        group_mi: Dict[str, float] = {}
        group_pmi: Dict[str, List[List[float]]] = {}
        if groups:
            counts = host["counts"]
            off = 0
            for group, idxs in groups:
                m = len(idxs)
                cs = _contingency_stats_np(counts[off:off + m])
                off += m
                group_cramers[group] = cs["cramers_v"]
                group_mi[group] = cs["mutual_info"]
                group_pmi[group] = [
                    [round(float(x), 6) for x in r]
                    for r in cs["pointwise_mutual_info"]]
                for j, i_col in enumerate(idxs):
                    cramers_by_col[i_col] = cs["cramers_v"]
                    rule_conf_by_col[i_col] = cs["max_rule_confidence"][j]
                    support_by_col[i_col] = cs["support"][j]

        # removal reasons (reference ColumnStatistics.reasonsToRemove :783-832)
        reasons: Dict[int, List[str]] = {}

        def flag(i: int, why: str):
            reasons.setdefault(i, []).append(why)

        for i in range(d):
            if stats["variance"][i] < self.min_variance:
                flag(i, f"variance {stats['variance'][i]:.3g} below min {self.min_variance}")
            c = corr[i]
            if not np.isnan(c):
                if abs(c) > self.max_correlation:
                    flag(i, f"label correlation {c:.3f} above max {self.max_correlation} (leakage)")
                elif abs(c) < self.min_correlation:
                    flag(i, f"label correlation {c:.3f} below min {self.min_correlation}")
            if not np.isnan(cramers_by_col[i]) and cramers_by_col[i] > self.max_cramers_v:
                flag(i, f"Cramér's V {cramers_by_col[i]:.3f} above max {self.max_cramers_v}")
            if (not np.isnan(rule_conf_by_col[i])
                    and rule_conf_by_col[i] >= self.max_rule_confidence
                    and support_by_col[i] >= 0
                    and support_by_col[i] * n_sample >= self.min_required_rule_support):
                flag(i, f"association rule confidence {rule_conf_by_col[i]:.3f} "
                        f"at/above max {self.max_rule_confidence} (leakage)")

        # feature-group propagation (reference: if one indicator of a pivot
        # group leaks, the whole group goes). protect_text_shared_hash
        # exempts shared-hash text columns — a hash slot aggregates many
        # tokens, so a sibling's leak says nothing about it (reference
        # reasonsToRemove :821 + isTextSharedHash :840)
        if self.remove_feature_group and vm is not None and reasons:
            all_groups = vm.index_of_group()
            leak = {i for i, why in reasons.items()
                    if any("leakage" in w or "Cramér" in w for w in why)}
            for group, idxs in all_groups.items():
                if leak.intersection(idxs):
                    for i in idxs:
                        if i in reasons:
                            continue
                        if (self.protect_text_shared_hash
                                and _is_text_shared_hash(vm.columns[i])):
                            continue
                        flag(i, f"sibling column in group '{group}' flagged for leakage")

        to_remove = sorted(reasons) if self.remove_bad_features else []
        keep = [i for i in range(d) if i not in set(to_remove)]
        if not keep:
            raise ValueError(
                "SanityChecker would remove ALL feature columns — loosen thresholds")

        names = vm.column_names() if vm is not None else [f"c{i}" for i in range(d)]
        summary = SanityCheckerSummary(
            stats=ColumnStatistics(
                names=names,
                count=stats["count"].tolist(),
                mean=stats["mean"].tolist(),
                variance=stats["variance"].tolist(),
                min=stats["min"].tolist(),
                max=stats["max"].tolist()),
            categorical=CategoricalGroupStats(
                cramers_v={g: v for g, v in group_cramers.items()},
                mutual_info=group_mi,
                pointwise_mutual_info=group_pmi),
            correlations_with_label=[None if np.isnan(c) else float(c)
                                     for c in corr],
            correlation_type=("spearman" if self.correlation_type_spearman
                              else "pearson"),
            dropped=[names[i] for i in to_remove],
            reasons={names[i]: why for i, why in reasons.items()},
            sample_size=n_sample,
            feature_correlations=feature_corr,
        )
        model = SanityCheckerModel(keep_indices=keep, summary=summary)
        model.summary_metadata = summary.to_json()
        # diagnostic: how the stats pass was placed (asserted by the
        # multichip dryrun — 'data'-sharded under with_mesh)
        model._stats_input_sharding = sharding_note
        return self._finalize_model(model)


class SanityCheckerModel(AllowLabelAsInput, Transformer):
    """Index-keep filter (reference SanityCheckerModel.transformFn :707-717)."""

    output_type = OPVector

    def __init__(self, keep_indices: List[int], summary: Dict[str, Any], uid=None):
        super().__init__("sanityCheck", uid)
        self.keep_indices = list(keep_indices)
        self.summary = summary
        self.summary_metadata = summary

    def device_columnar(self, env):
        """Pure-jax dual for the fused serve program
        (local/scoring.compiled_score_function): index-keep slice."""
        import jax.numpy as jnp
        vals, mask = env[self.input_features[1].name]
        return vals[:, jnp.asarray(self.keep_indices)], mask

    def device_inputs(self):
        """Only the vector input is read at serve time (the label feeds the
        estimator, not the fitted filter)."""
        return [self.input_features[1].name]

    def transform_column(self, table: FeatureTable) -> Column:
        _, vec_f = self.input_features
        col = table[vec_f.name]
        keep = np.asarray(self.keep_indices)
        vm: Optional[VectorMetadata] = col.metadata.get("vector_meta")
        new_meta = {}
        if vm is not None:
            new_meta["vector_meta"] = VectorMetadata(
                self.get_output().name, vm.select(self.keep_indices).columns)
        vals = col.values
        if isinstance(vals, np.ndarray):
            out = np.ascontiguousarray(vals[:, keep])
        else:  # device array: slice on device, no host round-trip
            out = vals[:, jnp.asarray(keep)]
        return Column(OPVector, out, None, new_meta)

    def transform_row(self, row: Dict[str, Any]) -> Any:
        _, vec_f = self.input_features
        v = row.get(vec_f.name) or []
        return [float(v[i]) for i in self.keep_indices]

    def summary_pretty(self) -> str:
        s = self.summary
        lines = [f"-- SanityChecker ({self.uid}) --",
                 f"sample size: {s['sampleSize']}",
                 f"columns kept: {len(self.keep_indices)} / {len(s['names'])}"]
        if s["dropped"]:
            lines.append("dropped:")
            for name in s["dropped"]:
                lines.append(f"  {name}: " + "; ".join(s["reasons"][name]))
        return "\n".join(lines)
