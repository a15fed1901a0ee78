"""Residency: seconds of `fastpath.get_aligned` + `block_until_ready`, the
promotion of the aligned planes to HBM, by the host's clock."""


def read(ctx):
    return float(ctx["setup"]["promote_s"])
