"""Serving ladder, host side: self time of every `fastpath.*` / `impactpath.*` span
and `search.collect` / traced queries. Their `device.wait` children are not in
it: a self time leaves out what child spans cover."""

import span_reduce


def read(ctx):
    return span_reduce.layer_ms_per_query(ctx, "serving ladder")
