"""Device: union of the device-op intervals in the traced slice / queries
answered in it."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["queries"]:
        return None
    return 1e3 * t["busy_s"] / t["queries"]
