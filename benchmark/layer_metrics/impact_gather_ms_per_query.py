"""Serving ladder: device milliseconds under the stage `impact.gather`
(`ops.impact_score_blocks`' read of the kept posting blocks, in
`impact_program`: the slots' block map, the gathers of the documents and
the quantized impacts, the dequant multiply) / traced queries
(`launch_reduce.stages`). A program whose ops name no such stage reports
nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, "impact.gather")
