"""Serving ladder: `fastpath.rescore.device_wall_ms` delta / queries. A
host-clock wall that includes the sync: a host-side share, not device time."""


def read(ctx):
    w = ctx["window"]
    if not w["queries"]:
        return None
    return w["counters"].get("fastpath.rescore.device_wall_ms", 0.0) \
        / w["queries"]
