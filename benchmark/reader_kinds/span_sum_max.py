"""Reader kind ``span_sum_max``: ``span_sum``'s seconds per operation, but
the LARGEST over all the window's operations and not their median: a stall
in any one operation of the window shows, where the median hides it."""
from benchmark.readers import _in_op, _matches


def read(spec, r):
    hits = [s for s in r.spans if _matches(s, spec)]
    if not hits or not r.ops:
        return None
    return max(sum(s.dur_ns for s in hits if _in_op(r, s, op)) / 1e9
               for op in r.ops)
