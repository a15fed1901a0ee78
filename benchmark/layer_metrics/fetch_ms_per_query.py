"""Fetch: self time of `reduce`, `fetch_phase` and `search.respond` / traced
queries."""

import span_reduce


def read(ctx):
    return span_reduce.layer_ms_per_query(ctx, "fetch")
