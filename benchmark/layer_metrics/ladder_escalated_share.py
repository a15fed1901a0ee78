"""Serving ladder: share of the window's queries that left the pruned rungs
(`fastpath.pruned_escalated` + `fallback` deltas / queries), in %."""


def read(ctx):
    w = ctx["window"]
    if not w["queries"]:
        return None
    c = w["counters"]
    return 100.0 * (c.get("fastpath.pruned_escalated", 0)
                    + c.get("fastpath.fallback", 0)) / w["queries"]
