"""From the profiler's trace of a window's slice to numbers.

`reduce_file(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData`
(nothing but JAX) and gives, on the trace's own clock:

  window_s   first `bench.request` annotation's start to the last one's end
             (the benchmark writes one `TraceAnnotation` around each client
             call, so this is the traced slice of the measured window)
  requests   how many such annotations the trace holds
  busy_s     the union of the intervals in which an operation ran on a
             device (the `XLA Ops` line of each `/device:TPU:n` plane),
             clipped to the window and averaged over the device planes
  kernel_s   the summed device durations of the Pallas kernels in the
             window: the `XLA Ops` events that are Mosaic custom calls
             (`%fused_bm25_topk_impact.1 = ... custom-call(...)`; without a
             `name=` a `pallas_call` is named after its kernel function),
             averaged over the device planes
  module_s   per program (`XLA Modules` name, its hash dropped), seconds
  op_s       per operation (`XLA Ops` name, shortened), seconds
  breakdown  {"device_ops": the five programs and the five operations with
              most seconds,
              "idle_gaps": idle seconds inside requests and between them,
              then the longest single gaps, by what the host was doing}

What a trace of this program looks like on one v5e (read by hand, PR 24) is
in PERF.md section 5."""

from __future__ import annotations

import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
REQUEST = "bench.request"
# the program's three Pallas kernels (`ops/pallas_bm25.py`) carry no
# `name=`, so each shows as a custom call named after its function
KERNEL_OPS = ("%fused_bm25_",)
_OP = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])?.*?"
                 r"\s([a-z][a-z\-]*)\(")


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def is_kernel(op: str) -> bool:
    """Is this `XLA Ops` event (its whole HLO line) a Pallas kernel?"""
    return op.startswith(KERNEL_OPS) and " custom-call(" in op


def short_module(name: str) -> str:
    """`jit_run(17250395598174324247)` -> `jit_run`."""
    return name.split("(", 1)[0]


def short_op(name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO line; keep the op's
    name, its (first) result shape and its opcode."""
    m = _OP.match(name)
    if not m:
        return name[:60]
    return " ".join(x for x in (m.group(1), m.group(2), m.group(3)) if x)


def events_of(profile) -> tuple:
    """-> (per device plane: {"ops", "modules"}: [(name, start_ns,
    end_ns)] of its `XLA Ops` and `XLA Modules` lines,
    [(start_ns, end_ns)] of the request annotations on the host)."""
    devices, requests = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == REQUEST:
                        requests.append((ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    return devices, sorted(requests)


def reduce_events(devices: dict, requests: list) -> dict:
    if not requests:
        raise SystemExit("benchmark: the trace holds no bench.request "
                         "annotation")
    if not devices:
        raise SystemExit("benchmark: the trace holds no device plane")
    lo, hi = requests[0][0], max(b for _a, b in requests)
    busy_ns = kernel_ns = 0.0
    op_ns, module_ns, gaps = {}, {}, []

    def clipped(events):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in events
                if b > lo and a < hi]
    for dev in devices.values():
        for n, a, b in clipped(dev["modules"]):
            n = short_module(n)
            module_ns[n] = module_ns.get(n, 0.0) + (b - a)
        inside = clipped(dev["ops"])
        for n, a, b in inside:
            if is_kernel(n):
                kernel_ns += b - a
            n = short_op(n)
            op_ns[n] = op_ns.get(n, 0.0) + (b - a)
        merged = _union([(a, b) for _n, a, b in inside])
        busy_ns += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    nd = len(devices)
    in_req = _union(requests)

    def where(a, b):
        mid = (a + b) / 2
        return ("inside a request" if any(x <= mid < y for x, y in in_req)
                else "between requests (generator)")
    by_class = {}
    for a, b in gaps:
        w = where(a, b)
        by_class[w] = by_class.get(w, 0.0) + (b - a) / nd
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10 - len(by_class)]
    idle = [[f"{w}, all gaps", s / 1e9] for w, s in sorted(by_class.items())]
    idle += [[f"{where(a, b)}, one gap", (b - a) / 1e9] for a, b in longest]

    def ranked(ns: dict) -> list:
        return [[n, s / nd / 1e9]
                for n, s in sorted(ns.items(), key=lambda kv: -kv[1])]
    mods, ops = ranked(module_ns), ranked(op_ns)
    return {"window_s": (hi - lo) / 1e9, "requests": len(requests),
            "busy_s": busy_ns / nd / 1e9, "kernel_s": kernel_ns / nd / 1e9,
            "device_planes": nd,
            "module_s": dict(mods[:20]), "op_s": dict(ops[:40]),
            "breakdown": {
                "device_ops": [["program " + n, s] for n, s in mods[:5]]
                + [["op " + n, s] for n, s in ops[:5]],
                "idle_gaps": idle}}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_events(*events_of(ProfileData.from_file(path)))


def describe(path: str, top: int = 25) -> dict:
    """What a trace holds, for reading one by hand: planes, lines, event
    counts and each line's most frequent names."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names, n = {}, 0
            for ev in line.events:
                n += 1
                names[ev.name] = names.get(ev.name, 0) + 1
            lines[line.name] = {"events": n, "names": sorted(
                names.items(), key=lambda kv: -kv[1])[:top]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(describe(sys.argv[1]), indent=1))
