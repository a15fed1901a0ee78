"""Device: per launch, the time the device sat idle between the start of its
`device.dispatch` span and the start of its `XLA Modules` event (a launch
queued behind a running program counts nothing), summed / traced queries
(`launch_reduce.seam`). A program without the span reports nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.seam_ms_per_query(ctx, "launch_latency_s")
