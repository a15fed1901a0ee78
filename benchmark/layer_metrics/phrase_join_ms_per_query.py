"""Positions: device milliseconds under the stage `executor.phrase_join` of
`executor_program` (a `match_phrase`'s anchor window sliced from the
resident planes, the binary searches of the other terms' windows, the cost)
/ traced queries (`launch_reduce.stage_ms_per_query`). A program whose ops
name no such stage (the parent) reports nothing."""

import launch_reduce
import pmc_roofline


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, pmc_roofline.JOIN)
