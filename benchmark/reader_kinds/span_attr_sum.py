"""Reader kind ``span_attr_sum``: median over the window's operations of a
numeric attribute summed over the matching spans inside each, times
``scale``. Spans that lack the attribute count for nothing; None where no
matching span of the window carries it (the parent of the PR that adds the
attribute, or a run without the counter behind it)."""
from benchmark.readers import _in_op, _matches, _median


def read(spec, r):
    key = spec["attr"]
    hits = [s for s in r.spans if _matches(s, spec) and key in s.attrs]
    if not hits or not r.ops:
        return None
    scale = float(spec.get("scale", 1.0))
    return _median([scale * sum(float(s.attrs[key]) for s in hits
                                if _in_op(r, s, op)) for op in r.ops])
