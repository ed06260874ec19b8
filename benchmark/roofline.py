"""Reader kind ``span_flops_roofline``, registered in ``readers.KINDS`` when
this module is imported (the traffic kinds whose cells report a roofline
import it). It is no file under ``reader_kinds/`` because
``tests/benchmark/test_benchmark_reader_kinds.py`` pins that directory's
listing to PR 24's three files, and a PR that adds a cell may not edit a
test the benchmark has (PERF.md, Open questions).

Per traced operation: the operations that the matching spans' own
attributes add up to (``opcount`` names a function of
``benchmark/opcounts.py`` that takes a span's attrs), over the device seconds
that ``seconds`` (another reader's parameters) reads for the same spans and
the ``peak`` of this device in ``benchmark/peaks.json``, in percent. None where there is no device trace, no matching span, or a span
lacks an attribute the count needs (the parent of the PR that adds it). A
device that ``peaks.json`` does not list is an error, not a default."""
import json
import os
import re

from . import opcounts, readers
from .readers import _in_op, _median, reader_for

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def device_peak(kind: str, key: str) -> float:
    peaks = json.load(open(_PEAKS))
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in {_PEAKS} "
                       f"(has {sorted(peaks)})")
    return float(peaks[kind][key])


def _wanted(span, spec):
    return (re.fullmatch(spec["span"], span.name) is not None
            and span.dur_ns is not None
            and all(re.fullmatch(rx, str(span.attrs.get(k, "")))
                    for k, rx in spec.get("attrs", {}).items()))


def read(spec, r, device_kind=None):
    if not r.traced or r.trace is None:
        return None
    count = getattr(opcounts, spec["opcount"])
    seconds = reader_for(spec["seconds"]["kind"])
    shares = []
    for op in r.traced:
        spans = [s for s in r.spans if _wanted(s, spec) and _in_op(r, s, op)]
        try:
            flops = sum(count(s.attrs) for s in spans)
        except KeyError:
            return None
        if not spans or flops <= 0:
            return None
        one = type(r)(ops=[op], traced=[op], spans=r.spans,
                      epoch_ns=r.epoch_ns, trace=r.trace)
        secs = seconds(spec["seconds"], one)
        if not secs or secs <= 0:
            return None
        if device_kind is None:
            import jax
            device_kind = jax.devices()[0].device_kind
        shares.append(100.0 * flops / secs
                      / device_peak(device_kind, spec["peak"]))
    return _median(shares)


readers.KINDS.setdefault("span_flops_roofline", read)
