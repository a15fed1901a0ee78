"""Column executor: device seconds of the program `jit_executor_program`
(`compiler.run_segment`: filter masks, the sort key, top-k, bucket counts)
in the traced slice / queries answered in it."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["queries"]:
        return None
    seconds = t.get("module_s", {}).get("jit_executor_program")
    if seconds is None:
        return None
    return 1e3 * seconds / t["queries"]
