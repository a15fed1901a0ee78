"""Vector search: the `knn` stage's share of its HBM roofline, %: the bytes
the configured method has to read a query
(`vectorsearch_roofline.stage_bytes`: centroids plus the mean fill of the
probed lists) over this device's `hbm_bytes_per_s` (`peaks.json`), over the
device's time in the `knn.*` scopes a query. Bound by memory, not by the
matrix unit: at batch 1 a row is read once for one product. Nothing where
the program has no IVF build read-out, no `knn.*` counters or no scopes."""

import vectorsearch_roofline


def read(ctx):
    seconds = vectorsearch_roofline.scope_seconds(ctx)
    nbytes = vectorsearch_roofline.query_bytes(ctx)
    if not seconds or nbytes is None or not ctx["trace"]["queries"]:
        return None
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s * ctx["trace"]["queries"] / sum(seconds.values())
