"""Plan + jit cache: seconds of the warm-up requests (host trace, lowering,
compile or cache read, first-use builds), by the host's clock."""


def read(ctx):
    return float(ctx["warmup"]["seconds"])
