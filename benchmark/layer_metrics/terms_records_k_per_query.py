"""Column executor: `aggs.terms.records` delta / queries, in thousands: the
bucket records the host built from the launches' `terms`, `multi_terms` and
`composite` counts (a Python dict a bucket: `executor` for a partial that
is records, `aggregations.finalize` for one that stays arrays until the
response's buckets are known). The slots those counts were made in are
`aggs.terms.ordinals`; a program that builds a record a non-empty slot
reads thousands here where the responses name tens. A program without the
counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    records = w["counters"].get("aggs.terms.records")
    if records is None or not w["queries"]:
        return None
    return records / 1e3 / w["queries"]
