"""Host, from outside: (traced slice - device busy) / queries answered in it.
Times every host layer at once; per-layer spans replace it later."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["queries"]:
        return None
    return 1e3 * (t["window_s"] - t["busy_s"]) / t["queries"]
