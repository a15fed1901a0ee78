"""Column executor: 100 x `executor.agg_run_counted` /
`executor.agg_bucket_launches` over the window: of the date-histogram bucket
counts the launches of `executor_program` carried, the share whose bucket
plane is in row order and was counted as runs (`ops.aggs.run_counts`: block
sums and one read a boundary) and not by a scatter-add of one update a row.
A program without the counters reports nothing, and so does a window that
launched no such aggregation."""


def read(ctx):
    c = ctx["window"]["counters"]
    launched = c.get("executor.agg_bucket_launches")
    if not launched or "executor.agg_run_counted" not in c:
        return None
    return 100.0 * c["executor.agg_run_counted"] / launched
