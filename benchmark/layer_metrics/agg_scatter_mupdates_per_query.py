"""Column executor: `aggs.scatter.updates` delta / queries, in millions: the
rows the window's launches handed to scatters (one update a row and a
scatter: a bucket count by `ops.aggs.bucket_counts`, and under a bucket
aggregation each metric's count, minimum, maximum and a limb a sum), counted
at each launch from the static spec (`compiler._agg_cost`). The device's
time there is this count (8.7-8.8 ns an update on a v5e). A form that
replaces a scatter counts what it reads under `aggs.blocked.rows` instead. A
program without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    updates = w["counters"].get("aggs.scatter.updates")
    if updates is None or not w["queries"]:
        return None
    return updates / 1e6 / w["queries"]
