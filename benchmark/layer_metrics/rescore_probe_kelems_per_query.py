"""Serving ladder: `fastpath.rescore.device_probe_elems` delta / queries, in
thousands: elements the device rescore gathered in its binary searches
(QB * C * the summed probe depth of a launch's term slots). A program without
the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    elems = w["counters"].get("fastpath.rescore.device_probe_elems")
    if elems is None or not w["queries"]:
        return None
    return elems / 1e3 / w["queries"]
