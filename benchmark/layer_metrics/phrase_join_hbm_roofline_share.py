"""Positions: the join's share of its HBM roofline, %: the bytes an exact
phrase has to read (`pmc_roofline.query_bytes`: over the TRACED requests,
the mean of 4 bytes a posting of the rarest term and 4 a position of the
two rarest terms inside the documents that hold every term) over this
device's `hbm_bytes_per_s` (`peaks.json`), over the device's time in the
stages `executor.phrase_join` and `executor.phrase_accumulate` a traced
query: bytes and time of the same requests. Bound by memory (a compare and
an add a four-byte element); the program's join is bound by the latency of
single-element gathers, so this reads far under 1%. Nothing where no
window was noted or the ops name no such stage."""

import launch_reduce
import pmc_roofline


def read(ctx):
    ms = launch_reduce.stage_ms_per_query(ctx, pmc_roofline.JOIN,
                                          pmc_roofline.ACCUMULATE)
    if not ms:
        return None
    nbytes = pmc_roofline.query_bytes(ctx)
    if nbytes is None:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)
