"""Column executor: device milliseconds under the stages `executor.total`,
`executor.sort_key` and `executor.topk` of `executor_program` (the total,
the sort key with its mask, the top-k with its relayout and block maxima:
ROADMAP S12's passes) / traced queries (`launch_reduce.stages`). A program
whose ops name no such stage reports nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.stage_ms_per_query(
        ctx, "executor.total", "executor.sort_key", "executor.topk")
