"""Device: of the traced slice's device time (the union of its `XLA Ops`
events), the % under the ops whose provenance names a stage, a path
component under one of `launch_reduce.STAGE_PREFIXES` (`impact.` `rescore.`
`executor.` `aggs.` `knn.`: the `jax.named_scope`s of the programs that own
the device's time). Unions, not summed durations: a `while` and the ops of
its body are events of their own, and the `while` carries no provenance (it
takes its body's). Read beside the `device.dispatch` spans the stages came
with: a trace without them (the parent, whose `aggs.bucketed_sub` and
`knn.*` scopes alone would read 27% and 99%), or one in which no op names a
stage, reports nothing."""

import launch_reduce


def read(ctx):
    out = launch_reduce.stages_for_ctx(ctx)
    if out is None or not out["all_s"] \
            or launch_reduce.seam_for_ctx(ctx) is None:
        return None
    return 100.0 * out["scoped_s"] / out["all_s"]
