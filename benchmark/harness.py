"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
sets it up, drives its closed loop for the window, checks what the timed
path produced, and builds the result line.

Data only: a configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a per-layer metric ``layer_metrics/<name>.json``;
a traffic ``kind`` is ``kinds/<kind>.py``. Adding any of them is adding a
file and one entry in ``BENCHMARK.json``. A per-layer entry's ``workloads``
lists every cell whose traced run reads it: a reader is written once.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import manifest as manifest_files
from . import readers, tracered

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]      # layer_metrics files of this cell


_load_json = manifest_files.load_json
load_manifest = manifest_files.load_manifest


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, manifest: Dict[str, Any], name: str) -> Cell:
    """The cell ``name`` with its files, found by the manifest's names."""
    w, config_path, traffic_path, bench_dir = manifest_files.cell_files(
        root, manifest, name)
    config = _load_json(config_path)
    traffic = _load_json(traffic_path)
    e2e = [m for m in manifest["end_to_end"] if _in_cell(m, name)]
    layer = []
    for m in manifest["per_layer"]:
        if not _in_cell(m, name):
            continue
        spec = _load_json(os.path.join(bench_dir, "layer_metrics",
                                       m["name"] + ".json"))
        for key in ("name", "unit", "layer", "moves", "source", "better"):
            if spec[key] != m[key]:
                raise ValueError(
                    f"layer_metrics/{m['name']}.json says {key}="
                    f"{spec[key]!r}, BENCHMARK.json says {m[key]!r}")
        spec["bench_dir"] = bench_dir
        layer.append(spec)
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)


def loop_for(kind: str):
    if not re.fullmatch(r"[A-Za-z0-9_]+", kind):
        raise ValueError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"benchmark.kinds.{kind}").Loop


# ---------------------------------------------------------------------------
# jax.monitoring
# ---------------------------------------------------------------------------

class Monitor:
    """Every ``jax.monitoring`` event with the phase it arrived in. JAX's
    listeners cannot be taken off again, so one pair is registered for the
    process and forwards to the monitor installed last."""

    _installed: Optional["Monitor"] = None
    _hooked = False

    def __init__(self):
        self.phase = "setup"
        self.events: List[Tuple[str, str, float]] = []

    def install(self) -> "Monitor":
        if not Monitor._hooked:
            import jax.monitoring as jm
            jm.register_event_listener(
                lambda event, **kw: Monitor._record(event, 1.0))
            jm.register_event_duration_secs_listener(
                lambda event, secs, **kw: Monitor._record(event, secs))
            Monitor._hooked = True
        Monitor._installed = self
        return self

    @staticmethod
    def _record(event: str, value: float) -> None:
        m = Monitor._installed
        if m is not None:
            m.events.append((m.phase, event, float(value)))

    def count(self, phase: str, event: str) -> int:
        return sum(1 for p, e, _ in self.events if p == phase and e == event)


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared, beside its limit. ``limit`` None: the number is
    printed and holds nothing yet."""
    name: str
    value: float
    limit: Optional[float]
    sense: str = "max"           # "max": value <= limit; "min": value >= limit

    @property
    def ok(self) -> bool:
        if self.limit is None:
            return True
        if self.value != self.value:     # NaN never passes
            return False
        return (self.value <= self.limit if self.sense == "max"
                else self.value >= self.limit)

    def line(self) -> str:
        op = "<=" if self.sense == "max" else ">="
        return (f"check {self.name}: {self.value!r} {op} {self.limit!r} "
                f"{'ok' if self.ok else 'FAILED'}")


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    monitor: Monitor
    log: Callable[[str], None]


def configure_jax(root: str) -> str:
    """The persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``, else
    at the fixed ``<checkout>/.jax_cache`` (the program's own rule), and
    every program cached whatever its compile time."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> Dict[str, float]:
    """``memory_stats()`` of the fullest of the chips used."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    full = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    return {k: float(v) for k, v in full.items()
            if isinstance(v, (int, float))}


def _profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def drive_window(loop, ctx: Context, r: readers.Readings,
                 trace_dir: Optional[str]) -> None:
    """The closed loop: operations one after another until ``seconds`` have
    passed (and the traffic's ``min_ops`` are done); the one running then
    is finished and counted. In a traced run the first ``traced_ops``
    operations run under the profiler."""
    import jax
    traffic = ctx.cell.traffic
    n_traced = int(traffic.get("traced_ops", 1)) if trace_dir else 0
    collect = bool(traffic.get("collect_garbage_between_ops", True))
    min_ops = int(traffic.get("min_ops", 1))
    profiling = False
    if n_traced:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_profiler_options())
        profiling = True
    t_open = time.perf_counter_ns()
    try:
        while True:
            loop.prepare_op()
            if collect:
                gc.collect()
            a = time.perf_counter_ns()
            if profiling and not r.traced:
                with jax.profiler.TraceAnnotation(tracered.ANCHOR, t_ns=a):
                    loop.op()
            else:
                loop.op()
            b = time.perf_counter_ns()
            r.ops.append((a, b))
            if profiling:
                r.traced.append((a, b))
                if len(r.traced) >= n_traced:
                    jax.profiler.stop_trace()
                    profiling = False
            if (b - t_open) / 1e9 >= ctx.seconds and len(r.ops) >= min_ops:
                break
    finally:
        if profiling:
            jax.profiler.stop_trace()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = print
             ) -> Dict[str, Any]:
    """Set up, measure, check; returns the result line as a dict."""
    import jax
    from . import workflows

    monitor = Monitor().install()
    ctx = Context(cell, int(seed), float(seconds), bool(trace), monitor, log)
    workflows.enable_metrics()
    loop = loop_for(cell.traffic["kind"])(ctx)

    loop.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")

    r = readers.Readings(monitoring=monitor.events)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    workflows.enable_spans(trace)
    monitor.phase = "window"
    try:
        drive_window(loop, ctx, r, trace_dir)
        monitor.phase = "after"
        if trace:
            r.spans, r.epoch_ns = workflows.finished_spans()
            workflows.enable_spans(False)
            if r.traced:
                r.trace = tracered.read_xplane(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    r.memory = memory_peak(cell.chips)

    ops_s = [(a / 1e9, b / 1e9) for a, b in r.ops]
    log("op walls " + " ".join(f"{b - a:.4f}" for a, b in ops_s))
    checks: List[Check] = [Check(
        "compiles_in_window",
        float(monitor.count("window", COMPILE_EVENT)), 0.0)]
    checks += loop.check()
    for c in checks:
        log(c.line())
    failed = [c.name for c in checks if not c.ok]

    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = dict(loop.end_to_end(ops_s), setup_s=setup_s)
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for spec in cell.per_layer:
            v = readers.read_metric(spec, r)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}

    device = dict(device_info(), memory_peak_bytes=int(
        r.memory.get("peak_bytes_in_use", 0)))
    result: Dict[str, Any] = {
        "correct": not failed, "attempted": len(r.ops),
        "failed": loop.failed_ops, "metrics": metrics, "device": device}
    if trace and r.trace is not None and r.trace.anchor is not None:
        wins = r.traced_windows()
        busy = tracered.busy_seconds(r.trace, wins)
        device["busy_s"] = busy
        device["window_s"] = sum(b - a for a, b in wins) / 1e9
        spans = tracered.to_trace_clock(r.spans, r.trace.anchor, r.epoch_ns)
        idle = [g for p in r.trace.device_planes()[:1]
                for lo, hi in wins
                for g in tracered.gaps(
                    tracered.busy_intervals(r.trace, p), lo, hi)]
        result["breakdown"] = {
            "device_ops": tracered.top_ops(r.trace, wins),
            "idle_gaps": tracered.name_gaps(idle, spans)}
    if failed:
        log("not correct: " + ", ".join(failed))
    return result


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

def main(argv: List[str], t_start: float, root: str) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(root, load_manifest(root), args.workload)
    import transmogrifai_tpu  # noqa: F401  (absent: fail before any output)
    import jax
    configure_jax(root)
    backend = jax.default_backend()
    if backend != "tpu" or len(jax.devices()) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); jax "
              f"found backend {backend!r} with {len(jax.devices())} "
              f"device(s). No result.", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
