"""Plan + jit cache: programs compiled or read from the persistent cache
during warm-up."""


def read(ctx):
    return float(ctx["warmup"]["programs"])
