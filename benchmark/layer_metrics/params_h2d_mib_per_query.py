"""Column executor: `executor.params_h2d_bytes` delta / queries, in MiB: the
host numpy arrays and scalars the launches of `executor_program` were handed
(each is a host-to-device copy a request; a plane that lives on the device
is not counted). A program without the counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    nbytes = w["counters"].get("executor.params_h2d_bytes")
    if nbytes is None or not w["queries"]:
        return None
    return nbytes / float(1 << 20) / w["queries"]
