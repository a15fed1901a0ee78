"""Coordinator: self time of `indices:data/read/search` / `node.msearch` (insights,
cache key and lookup, backpressure, task registry, slowlog, cache put) / traced
queries."""

import span_reduce


def read(ctx):
    return span_reduce.layer_ms_per_query(ctx, "coordinator")
