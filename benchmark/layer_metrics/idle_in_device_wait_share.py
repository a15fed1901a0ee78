"""Device: of the device's idle time inside requests, the share that lies inside
`device.wait` spans: the host was already waiting and the device had nothing to
run (launch and transfer latency, not Python)."""

import span_reduce


def read(ctx):
    out = span_reduce.for_ctx(ctx)
    if out is None or not out["idle"]["in_requests_s"]:
        return None
    idle = out["idle"]
    return 100.0 * idle["by_span"].get("device.wait", 0.0) \
        / idle["in_requests_s"]
