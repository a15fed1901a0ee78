"""Residency: seconds of the host corpus + segment build (stands in for an
index load), by the host's clock."""


def read(ctx):
    return float(ctx["setup"]["build_s"])
