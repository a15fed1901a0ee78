"""Vector search: device milliseconds in the `knn.*` named scopes of
`compiler.emit`'s `knn` (`knn.centroids`, `knn.gather`, `knn.score`,
`knn.scatter`; `knn.scan` on the exact route) over the traced slice's
queries (`xplane_scopes`, through `vectorsearch_roofline.scope_seconds`).
A program whose ops name no such scope (the parent) reports nothing."""

import vectorsearch_roofline


def read(ctx):
    seconds = vectorsearch_roofline.scope_seconds(ctx)
    if not seconds or not ctx["trace"]["queries"]:
        return None
    return 1e3 * sum(seconds.values()) / ctx["trace"]["queries"]
