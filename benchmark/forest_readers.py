"""Reader kinds and the byte count of the tree-winner cell, registered in
``readers.KINDS`` when this module is imported (the forest traffic kind
imports it), beside ``roofline.py`` and for its reason: ``reader_kinds/`` is
pinned to PR 24's three files by a test the benchmark has, and
``bytecounts.py`` is an accepted file.

* ``module_by_launch_span``: device seconds per traced operation in which an
  operation ran (the ``XLA Ops`` line's union) INSIDE the whole-program
  events (the ``XLA Modules`` line) matching ``pattern``, for the programs
  launched under the spans matching ``pick``. Several spans launch a program
  of one name (the winner's predict: twice under ``evaluate.predict``, once
  under the closing transform's ``predict.parts``); the device runs programs
  in the order they were launched, so the events in start order are dealt to
  the spans matching ``spans`` in start order, one each. None where the two
  counts differ (the pairing would be a guess) or nothing matches.
* ``span_forest_bytes_roofline``: ``roofline_bytes``'s share with the count
  below: the bytes a kernel must move whatever implements it, from the
  launching spans' own attributes, over the device seconds that ``seconds``
  (another reader's parameters) reads and the ``peak`` of ``peaks.json``.
  None where a span lacks an attribute (the parent of the PR that adds it).

Each kind returns None where there is nothing to read and never raises for
that.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

from . import readers, tracered
from .readers import _in_op, _median


def descent_bytes(attrs: Dict[str, Any]) -> float:
    """What one predict of a tree ensemble must move: its ``rows`` x
    ``features`` float32 matrix read once and one float32 score a row
    written. The trees' tables, the bin codes a program makes on the way and
    the per-node work are not counted: a lower bound."""
    return float(attrs["rows"]) * (float(attrs["features"]) + 1.0) * 4.0


BYTECOUNTS = {f.__name__: f for f in (descent_bytes,)}


def module_by_launch_span(spec, r) -> Optional[float]:
    wins = r.traced_windows()
    if not wins:
        return None
    planes = [p for p in r.trace.device_planes()
              if tracered.busy_intervals(r.trace, p)]
    line = {p: tracered.pick_line(r.trace, p, tracered.MODULE_LINES)
            for p in planes}
    if not planes or None in line.values():
        return None
    rx = re.compile(spec["pattern"])
    vals = []
    for op, (lo, hi) in zip(r.traced, wins):
        spans = sorted((s for s in r.spans
                        if re.fullmatch(spec["spans"], s.name)
                        and s.dur_ns is not None and _in_op(r, s, op)),
                       key=lambda s: s.ts_ns)
        per_plane = []
        for p in planes:
            evs = sorted((e for e in r.trace.events
                          if e.plane == p and e.line == line[p]
                          and rx.search(e.name) and lo <= e.start_ns < hi),
                         key=lambda e: e.start_ns)
            if not evs:
                continue
            if len(evs) != len(spans):
                return None
            busy = tracered.busy_intervals(r.trace, p)
            per_plane.append(sum(
                tracered.total(tracered.clip(busy, e.start_ns,
                                             e.start_ns + e.dur_ns))
                for e, s in zip(evs, spans)
                if re.fullmatch(spec["pick"], s.name)) / 1e9)
        if not per_plane:
            return None
        vals.append(sum(per_plane) / len(per_plane))
    return _median(vals)


def span_forest_bytes_roofline(spec, r, device_kind=None) -> Optional[float]:
    from .roofline import _wanted, device_peak
    if not r.traced or r.trace is None:
        return None
    count = BYTECOUNTS[spec["bytecount"]]
    seconds = readers.reader_for(spec["seconds"]["kind"])
    shares = []
    for op in r.traced:
        spans = [s for s in r.spans if _wanted(s, spec) and _in_op(r, s, op)]
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


for _kind in (module_by_launch_span, span_forest_bytes_roofline):
    readers.KINDS.setdefault(_kind.__name__, _kind)
