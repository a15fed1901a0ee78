"""Column executor: self time of the spans `search.aggs.prepare` (an
aggregation tree bound to a segment on the host) and `search.aggs.partial`
(the device's output turned into a partial; its `device.wait` excluded) /
traced queries. Both lie inside layers the span metrics already count
(`plan + jit cache`, `serving ladder`): this is a part of those, not a
seventh addend. A program without the spans reports nothing."""

import span_reduce

SPANS = ("search.aggs.prepare", "search.aggs.partial")


def read(ctx):
    out = span_reduce.for_ctx(ctx)
    if out is None or not ctx["trace"]["queries"]:
        return None
    found = [out["spans"][s]["self_s"] for s in SPANS if s in out["spans"]]
    if not found:
        return None
    return 1e3 * sum(found) / ctx["trace"]["queries"]
