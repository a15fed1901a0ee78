"""Plan + jit cache: programs compiled or read from the persistent cache
inside the measured window (JAX monitoring events). Expected 0; above 0 the
warm-up missed a shape and the window paid its host trace and lowering."""


def read(ctx):
    return float(ctx["window"]["compile"]["programs"])
