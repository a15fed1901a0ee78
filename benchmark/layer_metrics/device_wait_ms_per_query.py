"""Device, seen from the host: total of the `device.wait` spans (the host blocked
in a `jax.device_get` / `np.asarray` of a device array) / traced queries."""

import span_reduce


def read(ctx):
    return span_reduce.layer_ms_per_query(ctx, "device")
