"""Transport: self time of `rest.search` / `rest.msearch` (deadline, wlm admission,
pipeline resolution, remediation admit, response pipeline) / traced queries."""

import span_reduce


def read(ctx):
    return span_reduce.layer_ms_per_query(ctx, "transport")
