"""Serving ladder: device milliseconds under the stage `rescore.probe` (the
binary searches of `ops/rescore.exact_rescore_batch`) / traced queries
(`launch_reduce.stages`): the time beside `rescore_probe_kelems_per_query`'s
count. A program whose ops name no such stage reports nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.stage_ms_per_query(ctx, "rescore.probe")
