"""Nested join: `nested.join_updates` delta / queries, in millions: the
updates the join's scatters take (the padded child slots of the segment's
block, times the scatters of the clause's `score_mode`: counted where a
`nested` node is bound to a segment, `compiler.prepare`). A program
without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    updates = w["counters"].get("nested.join_updates")
    if updates is None or not w["queries"]:
        return None
    return updates / 1e6 / w["queries"]
