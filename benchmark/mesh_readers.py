"""Reader kinds that tell the device planes of one trace apart, registered
in ``readers.KINDS`` when this module is imported (the mesh traffic kind
imports it), beside ``roofline.py`` and for its reason: ``reader_kinds/`` is
pinned to PR 24's three files by a test the benchmark has.

The accepted readers average over the planes that ran anything; a cell on
several chips also wants what differs between them. Each kind returns None
where there is nothing to read (no device trace, one plane where it needs
several to compare) and never raises for that.

* ``plane_op_sum``: seconds per traced operation of the single-operation
  events (the ``XLA Ops`` line) whose name matches ``pattern``, mean over the
  planes that ran anything. ``share_of_busy: true`` gives them as a
  percentage of the same planes' busy seconds instead.
* ``module_busy_sum``: seconds per traced operation in which an operation
  ran (the ``XLA Ops`` line's union) INSIDE the whole-program events (the
  ``XLA Modules`` line) whose name matches ``pattern``, mean over the planes
  that ran anything. A program's event on the modules line also holds what
  it waits for (an argument still on its way from the host, the other
  chips), so this is the program's share of ``train_device_busy_s``, and
  the shares of all programs add up to it.
* ``plane_busy_skew_pct``: (max - min) / max of the planes' busy seconds
  inside the traced operations, in percent; needs ``min_planes`` planes.
* ``chip_memory_min``: the smallest ``memory_stats()[key]`` over the
  cell's first ``chips`` devices, read when the metric is (the peak is the
  process's), times ``scale``. ``readers.memory_stat`` gives the fullest.
* ``span_chip_bytes_roofline``: a chip's share of its memory roofline for a
  program that runs on every chip at once (below); ``bytecount`` names the
  function here that turns a span's attributes into a chip's bytes
  (``refit_chip_bytes`` where it names none, ``take_chip_bytes``).
* ``program_counter_total``: a counter of the program's metrics registry,
  summed over its label sets, 0 where it never counted. It reads the
  program's registry (``observability.metrics``), the one thing here that
  ``Readings`` does not carry.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

from . import readers, tracered
from .readers import _median

#: HLO collective operations by the start of an ``XLA Ops`` event's name
#: (``%all-reduce.5 = ...``; the asynchronous pair reads ``-start``/``-done``,
#: and a reduce-scatter or an all-to-all that the compiler made asynchronous
#: is named after its wrapper: ``%async-collective-done.3``, 0.195 s a chip in
#: the mesh cell's train that the pattern missed until it was seen there)
COLLECTIVES = (r"^%?(all-reduce|all-gather|reduce-scatter|"
               r"collective-permute|all-to-all|async-collective)")


def _planes(r) -> List[str]:
    return [p for p in r.trace.device_planes()
            if tracered.busy_intervals(r.trace, p)]


def _busy_by_plane(r, wins) -> Dict[str, float]:
    return {p: sum(tracered.total(tracered.clip(
        tracered.busy_intervals(r.trace, p), lo, hi))
        for lo, hi in wins) / 1e9 for p in _planes(r)}


def plane_op_sum(spec, r) -> Optional[float]:
    wins = r.traced_windows()
    if not wins:
        return None
    planes = _planes(r)
    if not planes:
        return None
    rx = re.compile(spec.get("pattern", COLLECTIVES))
    line = {p: tracered.pick_line(r.trace, p, tracered.OP_LINES)
            for p in planes}
    secs = [0.0] * len(wins)
    for e in r.trace.events:
        if (e.plane in line and line[e.plane] in (None, e.line)
                and rx.search(e.name)):
            for i, (lo, hi) in enumerate(wins):
                if lo <= e.start_ns < hi:
                    secs[i] += e.dur_ns / 1e9
    vals = []
    for win, total in zip(wins, secs):
        if spec.get("share_of_busy"):
            busy = sum(_busy_by_plane(r, [win]).values())
            if busy <= 0:
                return None
            vals.append(100.0 * total / busy)
        else:
            vals.append(total / len(planes))
    return _median(vals)


def module_busy_sum(spec, r) -> Optional[float]:
    wins = r.traced_windows()
    if not wins:
        return None
    planes = _planes(r)
    line = {p: tracered.pick_line(r.trace, p, tracered.MODULE_LINES)
            for p in planes}
    if not planes or None in line.values():
        return None
    rx = re.compile(spec["pattern"])
    modules = [e for e in r.trace.events
               if line.get(e.plane) == e.line and rx.search(e.name)]
    vals = []
    for lo, hi in wins:
        inside = sum(tracered.total(tracered.clip(
            tracered.busy_intervals(r.trace, e.plane), e.start_ns,
            e.start_ns + e.dur_ns))
            for e in modules if lo <= e.start_ns < hi)
        vals.append(inside / 1e9 / len(planes))
    return _median(vals) if any(v > 0 for v in vals) else None


def plane_busy_skew_pct(spec, r) -> Optional[float]:
    wins = r.traced_windows()
    if not wins:
        return None
    vals = []
    for win in wins:
        busy = list(_busy_by_plane(r, [win]).values())
        if len(busy) < int(spec.get("min_planes", 2)) or max(busy) <= 0:
            return None
        vals.append(100.0 * (max(busy) - min(busy)) / max(busy))
    return _median(vals)


def chip_memory_min(spec, r) -> Optional[float]:
    if not r.memory:                 # the harness read no device either
        return None
    import jax
    stats = [d.memory_stats() or {}
             for d in jax.local_devices()[:int(spec["chips"])]]
    vals = [s.get(spec["key"]) for s in stats]
    if not vals or any(v is None for v in vals):
        return None
    return float(min(vals)) * float(spec.get("scale", 1.0))


def program_counter_total(spec, r) -> Optional[float]:
    if not r.ops:
        return None
    from transmogrifai_tpu.observability import metrics as obs_metrics
    snap = obs_metrics.registry().snapshot()
    return float(sum(snap.get(spec["counter"], {}).values()))


def refit_chip_bytes(attrs) -> float:
    """What one chip's passes of a refit must read of its rows of the
    float32 feature matrix: ``rowsPerChip x features x 4 x matrixPasses``,
    the span's own counts (the solver's schedule; the label, the weights and
    the per-row temporaries are not counted: a lower bound). KeyError where
    an attribute is missing, which the reader takes as nothing to read."""
    return (float(attrs["rowsPerChip"]) * float(attrs["features"]) * 4.0
            * float(attrs["matrixPasses"]))


def take_chip_bytes(attrs) -> float:
    """What one chip must move for its shard of a row gather's result
    (``take_rows``): each of its ``rowsPerChip`` rows of ``rowBytes`` bytes
    read once, wherever it lies, and written once. What the program does
    beyond that (every chip looks up every wanted row and a reduce-scatter
    sums ``shards`` candidates of each) is not counted: a lower bound."""
    return 2.0 * float(attrs["rowsPerChip"]) * float(attrs["rowBytes"])


def span_chip_bytes_roofline(spec, r, device_kind=None) -> Optional[float]:
    """``roofline_bytes``'s share for a program that runs on every chip at
    once: a chip's bytes (``bytecount`` of the matching spans) over a
    chip's seconds (``seconds``: another reader's parameters, a mean over
    the planes) and ONE chip's ``peak`` of ``peaks.json``, in percent."""
    from .roofline import _wanted, device_peak
    if not r.traced or r.trace is None:
        return None
    seconds = readers.reader_for(spec["seconds"]["kind"])
    count = {f.__name__: f for f in (refit_chip_bytes, take_chip_bytes)}[
        spec.get("bytecount", "refit_chip_bytes")]
    shares = []
    for op in r.traced:
        spans = [s for s in r.spans
                 if _wanted(s, spec) and readers._in_op(r, s, op)]
        try:
            nbytes = sum(count(s.attrs) for s in spans)
        except KeyError:
            return None
        if not spans or nbytes <= 0:
            return None
        one = type(r)(ops=[op], traced=[op], spans=r.spans,
                      epoch_ns=r.epoch_ns, trace=r.trace)
        secs = seconds(spec["seconds"], one)
        if not secs or secs <= 0:
            return None
        if device_kind is None:
            import jax
            device_kind = jax.devices()[0].device_kind
        shares.append(100.0 * nbytes / secs
                      / device_peak(device_kind, spec["peak"]))
    return _median(shares)


for _kind in (plane_op_sum, module_busy_sum, plane_busy_skew_pct,
              chip_memory_min, program_counter_total,
              span_chip_bytes_roofline):
    readers.KINDS.setdefault(_kind.__name__, _kind)
