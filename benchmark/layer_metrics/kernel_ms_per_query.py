"""Kernels: summed device durations of the Pallas (Mosaic custom-call) events
in the traced slice / queries answered in it."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["queries"]:
        return None
    return 1e3 * t["kernel_s"] / t["queries"]
