"""Serving ladder: share of the window's queries the Pallas kernels served
(`fastpath.pure_served` + `bool_served` deltas / queries), in %."""


def read(ctx):
    w = ctx["window"]
    if not w["queries"]:
        return None
    c = w["counters"]
    return 100.0 * (c.get("fastpath.pure_served", 0)
                    + c.get("fastpath.bool_served", 0)) / w["queries"]
