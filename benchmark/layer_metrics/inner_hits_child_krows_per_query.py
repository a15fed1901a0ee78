"""Nested join: `nested.inner_hits_child_rows` delta / queries, in
thousands: the child rows the inner hits' launches gather and read back
(the blocks of a page's parents, padded to a power of two:
`executor._nested_inner_hits`), over all the window's queries, those
without inner hits too. Hundreds of rows a request with inner hits, not
the child space. A program without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    rows = w["counters"].get("nested.inner_hits_child_rows")
    if rows is None or not w["queries"]:
        return None
    return rows / 1e3 / w["queries"]
