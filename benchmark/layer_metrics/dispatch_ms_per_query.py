"""Device, seen from the host: summed durations of the `device.dispatch` spans
(one round every program call: flattening the argument tree, the host
arrays' copy up, the enqueue) / traced queries (`launch_reduce.seam`). A
program without the span (the parent) reports nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.seam_ms_per_query(ctx, "dispatch_s")
