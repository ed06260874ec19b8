"""Per-stage wall-clock report over the tracer's spans.

The analog of the reference's Spark-listener metrics collection (reference:
utils/.../spark/OpSparkListener.scala:55-110 — per-stage run time aggregated
into AppMetrics at app end, wired by OpWorkflowRunner.scala:139-154). The
clock is the tracer's (``observability/trace.py``): a profiled run switches
tracing on and its ``stage.fit`` / ``stage.transform`` / ``plan.segment``
spans are aggregated here when it ends, so a profiled run takes the same
path (planned segments included) as any other.
"""
from __future__ import annotations

import os
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List

from ..observability import trace as _trace

#: the spans a profiler aggregates: per-stage fits and transforms, and a
#: fused plan segment under its own name (its ``stages`` attr says how many
#: stages it ran as one program)
_STAGE_SPANS = ("stage.fit", "stage.transform", "plan.segment")


class StageProfiler:
    """Collects per-stage timings during fit/score (AppMetrics analog).

    Aggregates run forever in O(#stage classes) memory; raw per-op records are
    kept in a bounded ring so long-running streaming scorers don't grow
    without bound."""

    def __init__(self, max_records: int = 10_000):
        self.records: deque = deque(maxlen=max_records)
        self.app_start = time.time()
        self._total = 0.0
        self._count = 0
        self._by_stage: Dict[str, float] = {}
        self._by_layer: Dict[str, float] = {}
        self._by_op: Dict[str, float] = {}

    def collect(self, root: Any) -> None:
        """Aggregate the stage spans that ran under ``root`` (the
        ``workflow.train`` / ``workflow.score`` span of a finished run)."""
        lo, hi = root.ts_ns, root.ts_ns + (root.dur_ns or 0)
        for s in _trace.tracer().finished():
            if (s.name in _STAGE_SPANS and s.dur_ns is not None
                    and s.root_id == root.root_id and lo <= s.ts_ns <= hi):
                self._record(
                    s.attrs.get("stage", s.name), s.attrs.get("uid", "?"),
                    "fit" if s.name == "stage.fit" else "transform",
                    int(s.attrs.get("layer", -1)), s.ts_ns, s.dur_ns,
                    s.attrs.get("stages"))

    def _record(self, name: str, uid: str, op: str, layer: int, ts_ns: int,
                dur_ns: int, stages: Any = None) -> None:
        secs = dur_ns / 1e9
        rec = {"stage": name, "uid": uid, "op": op, "layer": layer,
               "seconds": secs,
               # microseconds on the tracer's clock: the Chrome-trace
               # timestamp of this op (see spans())
               "ts": ts_ns / 1e3}
        if stages is not None:
            rec["stages"] = stages
        self.records.append(rec)
        self._total += secs
        self._count += 1
        self._by_stage[name] = self._by_stage.get(name, 0.0) + secs
        self._by_op[op] = self._by_op.get(op, 0.0) + secs
        lk = f"layer_{layer}" if layer >= 0 else "unlayered"
        self._by_layer[lk] = self._by_layer.get(lk, 0.0) + secs

    @contextmanager
    def track(self, stage: Any, op: str, layer: int = -1):
        """Time one op by hand, outside any workflow run, on the same
        clock and into the same aggregates."""
        epoch = _trace.tracer().epoch_ns
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self._record(type(stage).__name__, getattr(stage, "uid", "?"),
                         op, layer, t0 - epoch, time.perf_counter_ns() - t0)

    def spans(self) -> List[Dict[str, Any]]:
        """The records ring as Chrome-trace complete events (``ph: "X"``,
        microsecond ``ts``/``dur``) — droppable straight into a trace-event
        document alongside the observability tracer's output. Bounded by the
        ring: only the newest ``maxlen`` ops survive a long run."""
        pid = os.getpid()
        return [{
            "name": f"{r['stage']}.{r['op']}",
            "ph": "X",
            "ts": r["ts"],
            "dur": r["seconds"] * 1e6,
            "pid": pid,
            "tid": 0,
            "args": {k: r[k] for k in ("uid", "op", "layer", "stages")
                     if k in r},
        } for r in self.records]

    # -- aggregation (reference AppMetrics, OpSparkListener.scala:55-110) ----
    def app_metrics(self) -> Dict[str, Any]:
        # accumulated as spans arrive (NOT derived from the bounded records
        # ring, which would undercount runs past its maxlen)
        by_layer = self._by_layer
        from ..observability import devicemem as _devicemem
        from ..observability import ledger as _ledger
        from .jax_cache import cache_stats
        led = _ledger.ledger()
        out = {
            "appDurationSecs": time.time() - self.app_start,
            "stageSecondsTotal": self._total,
            "byStage": dict(sorted(self._by_stage.items(), key=lambda kv: -kv[1])),
            "byOp": dict(self._by_op),
            "byLayer": dict(sorted(by_layer.items())),
            "numRecords": self._count,
            # span-compatible view of the (bounded) record ring + the
            # process compile accounting — the two blind spots of the
            # original wall-clock-sums-only report. Program-build counts
            # come from the compile ledger (backend-independent: the
            # dispatch sites report their own builds); the persistent-
            # cache listener's hits/misses ride along as a cross-check
            # where its monitoring events fire (TPU/GPU — they read 0 on
            # CPU, the pre-ledger gap; observability/ledger.py)
            "spans": self.spans(),
            "compileCache": {
                **cache_stats(),
                "builds": led.total,
                "byCause": led.counts_by_cause(),
                "bySubsystem": led.counts(),
            },
        }
        # device-side memory: measured live-buffer stats where the
        # backend reports them, plus the observatory's shape-predicted
        # per-subsystem peaks (works on every backend, CPU included)
        stats = _devicemem.memory_stats()
        if stats:
            out["deviceMemory"] = stats
        out["deviceMemoryPredicted"] = _devicemem.observatory().snapshot()
        return out

    def pretty(self, top_k: int = 15) -> str:
        m = self.app_metrics()
        lines = [f"Stage timings ({m['numRecords']} ops, "
                 f"{m['stageSecondsTotal']:.2f}s total):"]
        for name, secs in list(m["byStage"].items())[:top_k]:
            lines.append(f"  {secs:8.3f}s  {name}")
        return "\n".join(lines)
