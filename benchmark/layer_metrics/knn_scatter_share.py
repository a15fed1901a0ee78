"""Vector search: of the device time in the `knn.*` scopes, the % spent in
`knn.gather` (candidate rows fetched by doc id from a matrix in doc order)
and `knn.scatter` (scores and a mask written back into the doc space for
the plan's top-k): the part a list-contiguous layout with a top-k over the
candidates would do away with. Nothing where no op names a `knn.*` scope."""

import vectorsearch_roofline


def read(ctx):
    seconds = vectorsearch_roofline.scope_seconds(ctx)
    if not seconds:
        return None
    return 100.0 * (seconds["knn.gather"] + seconds["knn.scatter"]) \
        / sum(seconds.values())
