"""Nested join: the clause's share of its HBM roofline, %: the bytes a
`nested` clause has to move (`nested_roofline.query_bytes`: over the TRACED
requests, 8 bytes of `answers.date` and 4 of the parent map an answer row,
4 written a question, for every clause they carry) over this device's
`hbm_bytes_per_s` (`peaks.json`), over the device's time in the stages
`executor.nested_child` and `executor.nested_join` a traced query: bytes
and time of the same requests. Bound by memory (a compare and an add an
element); the program's join is a scatter update a child slot, so this
reads far under 1%. Nothing where no window was noted or the ops name no
such stage."""

import launch_reduce
import nested_roofline


def read(ctx):
    ms = launch_reduce.stage_ms_per_query(ctx, nested_roofline.CHILD,
                                          nested_roofline.JOIN)
    if not ms:
        return None
    nbytes = nested_roofline.query_bytes(ctx)
    if not nbytes:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)
