"""Reader kind ``span_bytes_roofline``, registered in ``readers.KINDS`` when
this module is imported (the traffic kinds whose cells report it import it),
beside ``roofline.py`` and for its reason: ``reader_kinds/`` is pinned to
PR 24's three files by a test the benchmark has.

Per traced operation: the bytes that the matching spans' own attributes add
up to (``bytecount`` names a function of ``benchmark/bytecounts.py`` that
takes a span's attrs), over the device seconds that ``seconds`` (another
reader's parameters) reads for the same spans and the ``peak`` of this
device in ``benchmark/peaks.json``, in percent. None where there is no
device trace, no matching span, or a span lacks an attribute the count needs
(the parent of the PR that adds it). A device that ``peaks.json`` does not
list is an error, not a default."""
from . import bytecounts, readers
from .readers import _in_op, _median, reader_for
from .roofline import _wanted, device_peak


def read(spec, r, device_kind=None):
    if not r.traced or r.trace is None:
        return None
    count = getattr(bytecounts, spec["bytecount"])
    seconds = reader_for(spec["seconds"]["kind"])
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


readers.KINDS.setdefault("span_bytes_roofline", read)
