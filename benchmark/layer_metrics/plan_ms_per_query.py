"""Plan + jit cache: self time of `search.plan`, `search.prepare` and `query_phase`
(parse, rewrite, spec building, the XLA path's prepare and program dispatch) /
traced queries."""

import span_reduce


def read(ctx):
    return span_reduce.layer_ms_per_query(ctx, "plan + jit cache")
