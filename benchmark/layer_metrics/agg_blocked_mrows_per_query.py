"""Column executor: `aggs.blocked.rows` delta / queries, in millions: the
rows the window's launches read in a form that replaces a scatter, counted
at each launch from the static spec (`compiler._agg_cost`) as rows x passes
over them: one pass a bucket count, and one for all of a bucketed metric's
accumulators, where the bucket count is small enough to compare a block of
rows against every bucket while it is on the chip (`ops.aggs`' dense form),
and one pass for `ops.aggs.run_counts` over a plane in row order. What still
scatters counts under `aggs.scatter.updates` instead. A program without the
counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    rows = w["counters"].get("aggs.blocked.rows")
    if rows is None or not w["queries"]:
        return None
    return rows / 1e6 / w["queries"]
