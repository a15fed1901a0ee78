"""From the program's spans on the profiler's trace to per-layer numbers.

The program (`opensearch_tpu/utils/trace.py`) writes every span as a
`TraceAnnotation("ostpu:" + name)`, so a traced run's `.xplane.pb` holds
them on the `/host:CPU` plane beside the benchmark's `bench.request` and on
one clock with the device's `XLA Ops`. `reduce_file(path)` nests the host
events of each thread line by interval containment, keeps what lies inside
whole traced requests, and gives:

  spans    per span name: count, total_s, self_s (a span's duration minus
           what its child spans cover)
  layers   per layer of PERF.md section 3: the summed self_s of its spans
           (`LAYERS` below; a name the table does not know counts to the
           nearest enclosing known layer and is listed under `unknown`);
           `harness` is the self time of `bench.request`, the benchmark's
           own `send`
  idle     the device's idle time inside the window (the complement of the
           `XLA Ops` union, as `trace_reduce` takes it), apportioned to the
           innermost span covering each instant: `by_span`, `by_layer`,
           `in_requests_s`, `between_requests_s`. One idle gap runs from
           one request's last kernel to the next one's first and crosses a
           dozen spans, so a gap is split, never assigned by its middle.

The self times of one thread partition its requests exactly. Spans on
other threads (pool workers) are counted in `spans` and `layers` too, and
`threads` says how many lines held spans; the idle time is laid over the
line that holds the requests. A trace of a program without the spans (an
older commit) reduces to None.

    python3 benchmark/span_reduce.py <xplane.pb>     # the three tables
"""

from __future__ import annotations

import glob
import os

from trace_reduce import REQUEST, _union, events_of

PREFIX = "ostpu:"
HARNESS = "harness"
# span name (or prefix ending in ".") -> layer, in PERF.md section 3's words
LAYERS = {
    "rest.search": "transport", "rest.msearch": "transport",
    "indices:data/read/search": "coordinator", "node.msearch": "coordinator",
    "search.plan": "plan + jit cache", "search.prepare": "plan + jit cache",
    "query_phase": "plan + jit cache",
    "fastpath.": "serving ladder", "impactpath.": "serving ladder",
    "search.collect": "serving ladder",
    "device.wait": "device",
    "reduce": "fetch", "fetch_phase": "fetch", "search.respond": "fetch",
}
OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark_out")


def layer_of(name: str):
    """The layer of a span name, None where the table does not know it."""
    if name == REQUEST:
        return HARNESS
    return LAYERS.get(name) or LAYERS.get(name.split(".", 1)[0] + ".")


class Node:
    __slots__ = ("name", "start", "end", "children", "layer")

    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, start, end
        self.children, self.layer = [], None

    def self_ns(self):
        return (self.end - self.start) - sum(c.end - c.start
                                             for c in self.children)


def host_lines(profile) -> list:
    """Per thread line of the host planes: [(name, start_ns, end_ns)] of
    the program's spans (prefix dropped) and the request annotations."""
    lines = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    name = name[len(PREFIX):]
                elif name != REQUEST:
                    continue
                evs.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
            if evs:
                lines.append(evs)
    return lines


def nest(events: list) -> list:
    """One thread's events nested by interval containment -> its top-level
    nodes in time order. A thread's spans are properly nested; an event
    that outlives its parent (a clock artefact) is clipped to it."""
    roots, stack = [], []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and a >= stack[-1].end:
            stack.pop()
        if stack:
            b = min(b, stack[-1].end)
        node = Node(name, a, b)
        (stack[-1].children if stack else roots).append(node)
        stack.append(node)
    return roots


def _walk(node, layer, out):
    node.layer = layer_of(node.name) or layer
    out.append(node)
    for ch in node.children:
        _walk(ch, node.layer, out)


def _self_segments(node, out):
    cur = node.start
    for ch in node.children:
        if ch.start > cur:
            out.append((cur, ch.start, node))
        _self_segments(ch, out)
        cur = max(cur, ch.end)
    if node.end > cur:
        out.append((cur, node.end, node))


def _idle(devices: dict, lo: int, hi: int) -> list:
    """[(start, end)] of every device's idle intervals inside [lo, hi], as
    `trace_reduce.reduce_events` takes them."""
    gaps = []
    for dev in devices.values():
        merged = _union([(max(a, lo), min(b, hi)) for _n, a, b in dev["ops"]
                         if b > lo and a < hi])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return sorted(gaps)


def reduce_events(devices: dict, lines: list):
    """`devices` as `trace_reduce.events_of` gives them, `lines` as
    `host_lines`. None where the trace holds no program span."""
    if not any(name != REQUEST for evs in lines for name, _a, _b in evs):
        return None
    forests = [nest(evs) for evs in lines]
    # a request is a `bench.request`; in a trace of a node that nothing
    # annotated from outside, every top-level span
    annotated = any(n.name == REQUEST for f in forests for n in f)

    def is_request(n):
        return n.name == REQUEST or not annotated
    requests = sorted((n.start, n.end) for f in forests for n in f
                      if is_request(n))
    lo, hi = requests[0][0], max(b for _a, b in requests)
    # the caller's line: the one with the requests (the most, if several)
    caller = max(forests, key=lambda f: sum(map(is_request, f)))
    nodes = []
    for forest in forests:
        for root in forest:
            if forest is caller and not is_request(root):
                continue        # outside every request: warm-up, the check
            if root.end > lo and root.start < hi:
                _walk(root, None, nodes)
    spans, layers, unknown = {}, {}, set()
    for n in nodes:
        row = spans.setdefault(n.name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += (n.end - n.start) / 1e9
        row["self_s"] += n.self_ns() / 1e9
        if layer_of(n.name) is None:
            unknown.add(n.name)
        key = n.layer or "unattributed"
        layers[key] = layers.get(key, 0.0) + n.self_ns() / 1e9

    segments, cur = [], lo      # the caller's timeline, innermost span
    for root in caller:
        if not is_request(root):
            continue
        if root.start > cur:
            segments.append((cur, root.start, None))
        _self_segments(root, segments)
        cur = max(cur, root.end)
    by_span, by_layer, between, i = {}, {}, 0.0, 0
    nd = max(len(devices), 1)
    for a, b in _idle(devices, lo, hi):
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            s, e, node = segments[j]
            part = (min(b, e) - max(a, s)) / nd / 1e9
            if node is None:
                between += part
            else:
                by_span[node.name] = by_span.get(node.name, 0.0) + part
                key = node.layer or "unattributed"
                by_layer[key] = by_layer.get(key, 0.0) + part
            j += 1
    tops = [n for r in caller if is_request(r)
            for n in (r.children if annotated else [r])]
    return {"window_s": (hi - lo) / 1e9, "requests": len(requests),
            "request_s": sum(b - a for a, b in requests) / 1e9,
            "program_s": sum(n.end - n.start for n in tops) / 1e9,
            "threads": len(forests),
            "spans": spans, "layers": layers, "unknown": sorted(unknown),
            "idle": {"in_requests_s": sum(by_span.values()),
                     "between_requests_s": between,
                     "by_span": by_span, "by_layer": by_layer}}


def reduce_file(path: str):
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    devices, _requests = events_of(profile)
    return reduce_events(devices, host_lines(profile))


_memo: dict = {}


def for_ctx(ctx: dict):
    """The reduction of this run's trace for a per-layer reader: `ctx`
    carries no path, so the newest `.xplane.pb` under `benchmark_out/` is
    taken (a run is its own process and clears its trace directory before
    it records) and held to the run's own count of traced requests; parsed
    once a process. None where there is no such trace, or no span in it."""
    found = glob.glob(os.path.join(OUT_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not found or not ctx.get("trace"):
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _memo:
        _memo.clear()
        _memo[key] = reduce_file(path)
    out = _memo[key]
    if out is None or out["requests"] != ctx["trace"]["requests"]:
        return None
    return out


def layer_ms_per_query(ctx: dict, layer: str):
    """Self time of `layer`'s spans / traced queries, for the readers."""
    out = for_ctx(ctx)
    if out is None or not ctx["trace"]["queries"]:
        return None
    return 1e3 * out["layers"].get(layer, 0.0) / ctx["trace"]["queries"]


def tables(out: dict) -> str:
    n = out["requests"]
    rows = [f"{n} requests, window {out['window_s']:.4f} s, in requests "
            f"{out['request_s']:.4f} s, in the program {out['program_s']:.4f}"
            f" s, {out['threads']} thread line(s); ms are per request", "",
            f"{'span':34}{'count':>8}{'total ms':>12}{'self ms':>12}"]
    for name, r in sorted(out["spans"].items(),
                          key=lambda kv: -kv[1]["self_s"]):
        rows.append(f"{name:34}{r['count']:8d}{1e3 * r['total_s'] / n:12.4f}"
                    f"{1e3 * r['self_s'] / n:12.4f}")
    idle = out["idle"]
    rows += ["", f"{'layer':34}{'self ms':>12}{'idle ms':>12}"]
    for name, s in sorted(out["layers"].items(), key=lambda kv: -kv[1]):
        rows.append(f"{name:34}{1e3 * s / n:12.4f}"
                    f"{1e3 * idle['by_layer'].get(name, 0.0) / n:12.4f}")
    rows += ["", f"device idle {idle['in_requests_s']:.4f} s inside "
             f"requests, {idle['between_requests_s']:.4f} s between them; "
             f"by the innermost span covering it:",
             f"{'span':34}{'idle s':>12}{'share %':>10}"]
    for name, s in sorted(idle["by_span"].items(), key=lambda kv: -kv[1]):
        rows.append(f"{name:34}{s:12.4f}"
                    f"{100 * s / max(idle['in_requests_s'], 1e-12):10.2f}")
    if out["unknown"]:
        rows += ["", "spans the layer table does not know: "
                 + ", ".join(out["unknown"])]
    return "\n".join(rows)


if __name__ == "__main__":
    import sys
    reduced = reduce_file(sys.argv[1])
    print("the trace holds no program span" if reduced is None
          else tables(reduced))
