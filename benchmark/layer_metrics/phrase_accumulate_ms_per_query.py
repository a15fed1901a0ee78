"""Positions: device milliseconds under the stage
`executor.phrase_accumulate` of `executor_program` (the scatter-add of the
anchors' weights into the document plane) / traced queries
(`launch_reduce.stage_ms_per_query`). A program whose ops name no such
stage (the parent) reports nothing."""

import launch_reduce
import pmc_roofline


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, pmc_roofline.ACCUMULATE)
