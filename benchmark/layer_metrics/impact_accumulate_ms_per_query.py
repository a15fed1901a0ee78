"""Serving ladder: device milliseconds under the stage `impact.accumulate`
(the two scatter-adds of `ops.impact_score_blocks`, in `impact_program`) /
traced queries (`launch_reduce.stages`). A program whose ops name no such
stage reports nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, "impact.accumulate")
