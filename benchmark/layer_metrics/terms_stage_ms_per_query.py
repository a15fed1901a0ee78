"""Column executor: device milliseconds under the stage `aggs.terms` of
`executor_program` (a `terms`, `multi_terms`, `composite` or keyword
`cardinality` group-by, whole: the match gathered by value, the bucket ids,
the count in its dense or scatter form, a cardinality's registers) / traced
queries (`launch_reduce.stages`). A program whose ops name no such stage
(the parent) reports nothing."""

import big5_roofline
import launch_reduce


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, big5_roofline.STAGE)
