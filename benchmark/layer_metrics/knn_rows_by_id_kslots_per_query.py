"""Vector search: `knn.rows_by_id` delta / queries, in thousands: the
candidate rows a launch fetches one doc id at a time out of the doc-ordered
matrix (and writes out again before the product reads them). A probe that
reads its lists where they lie, as dense windows of rows stored in list
order, fetches none: 0. Counted at each launch from the static spec
(`compiler._count_knn`). A program without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    rows = w["counters"].get("knn.rows_by_id")
    if rows is None or not w["queries"]:
        return None
    return rows / 1e3 / w["queries"]
