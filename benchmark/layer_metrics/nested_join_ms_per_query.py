"""Nested join: device milliseconds under the stage `executor.nested_join`
of `executor_program` (child to parent: the count of a parent's matching
children and the sum or extreme of their scores, a scatter update a child
slot each) / traced queries (`launch_reduce.stage_ms_per_query`). A program
whose ops name no such stage (the parent) reports nothing."""

import launch_reduce
import nested_roofline


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, nested_roofline.JOIN)
