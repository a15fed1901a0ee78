"""Column executor: of the traced slice's device time (the `XLA Ops` events'
summed durations), the % spent in the ops of metric sub-aggregations under
bucket aggregations: those whose provenance names the scope
`aggs.bucketed_sub` (`ops.aggs.bucketed_sub_metric`: per-bucket count,
minimum, maximum and the limbs of a sum, a scatter each). Read from the
trace file itself (`xplane_scopes`: the scope is a stat of an event's
metadata). A program whose ops name no such scope (the parent), or a trace
with no device op, reports nothing."""

import glob
import os

import span_reduce
import xplane_scopes

SCOPE = "aggs.bucketed_sub"


def read(ctx):
    found = glob.glob(os.path.join(span_reduce.OUT_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not found or not ctx.get("trace"):
        return None
    try:
        out = xplane_scopes.scope_seconds(max(found, key=os.path.getmtime),
                                          SCOPE)
    except (ValueError, IndexError, OSError):
        return None
    if out is None or not out[0] or not out[1]:
        return None
    return 100.0 * out[0] / out[1]
