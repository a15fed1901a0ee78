"""Column executor: the `aggs.terms` stage's share of its HBM roofline, %:
the bytes a query's group-by has to read (`big5_roofline.query_bytes`: the
padded rows times the planes the seven operations name, from the
configuration's shapes and the window's launch counter) over this device's
`hbm_bytes_per_s` (`peaks.json`), over the device's time in the stage a
traced query. Bound by memory (an add a four-byte element). Nothing where
the program has no `aggs.terms.*` counters or its ops name no such stage."""

import big5_roofline
import launch_reduce


def read(ctx):
    nbytes = big5_roofline.query_bytes(ctx)
    ms = launch_reduce.stage_ms_per_query(ctx, big5_roofline.STAGE)
    if nbytes is None or not ms:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)
