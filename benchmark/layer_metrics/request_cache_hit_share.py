"""Coordinator: share of the window's requests the request cache answered
(`request_cache.stats()` delta / lookups), in %. Expected 0: it guards the
benchmark against timing the cache."""


def read(ctx):
    c = ctx["window"]["counters"]
    hits = c.get("request_cache.hit_count", 0)
    looked = hits + c.get("request_cache.miss_count", 0)
    return 100.0 * hits / looked if looked else 0.0
