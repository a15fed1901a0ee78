"""Device: per `device.wait`, from the end of the last `XLA Modules` event it
waited for to the span's end, clipped to the span (the copy down and the
wake-up), summed / traced queries (`launch_reduce.seam`). Read only beside
`device.dispatch` spans: a program without them reports nothing."""

import launch_reduce


def read(ctx):
    return launch_reduce.seam_ms_per_query(ctx, "readback_s")
