"""Reader kind ``device_op_by_span_order``: device seconds of the programs
that the matching spans launched, found by ORDER and not by name.

The sweep's family programs are all named ``jit_prog`` on the modules line.
Each ``sweep.family`` span (named by ``span``) carries ``order`` (its place
among the families dispatched) and ``programs`` (how many device programs it
launched), and the device runs programs in the order they were launched. So
per traced operation the events matching ``pattern`` on ``line`` are taken in
start order and dealt out to the operation's spans in ``order``; the seconds
of those that fall to the spans matching ``attrs`` are summed. None when the
number of events is not the number of programs the spans launched (the
pairing would then be a guess), or where the spans carry no ``order``."""
import re

from benchmark import tracered
from benchmark.readers import _in_op, _median


def _wanted(span, attrs):
    return all(re.fullmatch(rx, str(span.attrs.get(k, "")))
               for k, rx in attrs.items())


def read(spec, r):
    wins = r.traced_windows()
    if not wins:
        return None
    rx, lrx = re.compile(spec["pattern"]), re.compile(spec["line"])
    planes = [p for p in r.trace.device_planes()
              if tracered.busy_intervals(r.trace, p)]
    vals = []
    for op, (lo, hi) in zip(r.traced, wins):
        spans = [s for s in r.spans
                 if re.fullmatch(spec["span"], s.name) and _in_op(r, s, op)
                 and s.dur_ns is not None and "order" in s.attrs]
        if not spans:
            return None
        spans.sort(key=lambda s: int(s.attrs["order"]))
        owners = [s for s in spans
                  for _ in range(int(s.attrs.get("programs", 1)))]
        per_plane = []
        for p in planes:
            evs = sorted((e.start_ns, e.dur_ns) for e in r.trace.events
                         if e.plane == p and lrx.fullmatch(e.line)
                         and rx.search(e.name) and lo <= e.start_ns < hi)
            if not evs:
                continue
            if len(evs) != len(owners):
                return None
            per_plane.append(sum(
                dur for (_, dur), s in zip(evs, owners)
                if _wanted(s, spec.get("attrs", {}))) / 1e9)
        if not per_plane:
            return None
        vals.append(sum(per_plane) / len(per_plane))
    return _median(vals)
