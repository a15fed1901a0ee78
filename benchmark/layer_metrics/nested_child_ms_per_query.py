"""Nested join: device milliseconds under the stage `executor.nested_child`
of `executor_program` (the child clause's mask over the child space, under
the children's own liveness) / traced queries
(`launch_reduce.stage_ms_per_query`). Where the compiler fuses the clause's
compare into the join's first pass, as it does for the cell's `range`, no op
names the stage and its time lies under `executor.nested_join`: 0 then. A
program whose ops name neither stage (the parent) reports nothing."""

import launch_reduce
import nested_roofline


def read(ctx):
    ms = launch_reduce.stage_ms_per_query(ctx, nested_roofline.CHILD)
    if ms is None and launch_reduce.stage_ms_per_query(
            ctx, nested_roofline.JOIN) is not None:
        return 0.0
    return ms
