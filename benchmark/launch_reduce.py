"""A launch, end to end, on the trace's one clock.

The program writes one `device.dispatch` span round every program call
(`compiler._TimedProgram.__call__`, the kernel launches of
`search/fastpath.py`) and reads every result inside a `device.wait`; the
device writes one `XLA Modules` event a launch and one `XLA Ops` event an
op, whose provenance (a stat of the event's metadata) names the
`jax.named_scope`s it was traced under. Two reductions of one `.xplane.pb`:

`seam(devices, lines)`, over the events `trace_reduce.events_of` and
`span_reduce.host_lines` give, inside whole traced requests:

  dispatch_s        the `device.dispatch` spans' summed durations
  launch_latency_s  per launch, from the start of its dispatch span (or
                    from the end of the program before it on the device,
                    if that is later: a launch queued behind a running
                    program counts nothing) to the start of its module
                    event. Launches and module events are matched in order
                    on the one stream; a module event that started before
                    the next dispatch did has no launch (an eager op) and
                    is listed under `unmatched_modules`
  readback_s        per `device.wait`, from the end of the last module
                    event that ended inside or before it to the span's
                    end, clipped to the span: the copy down and the wake-up
  gap_s             what is left of a wait's region with no program on the
                    device: between one program's end and the next one's
                    dispatch, and from the last program's end to the
                    wait's start (the host came late to a finished device)
  idle_s            the device's idle time (the complement of the `XLA
                    Ops` union, as `span_reduce` lays it) over the same
                    regions: for each wait, from the dispatch start of the
                    first launch since the wait before it (its own start,
                    if it reads what an earlier wait read) to its end.
                    `launch_latency_s + gap_s + readback_s` is the same
                    time taken from the module events and the spans alone;
                    `identity_error` is their relative difference

`stages(path)`, from the file's wire format (`xplane_scopes.fields`):
device seconds by stage, a stage being a component `<program>.<stage>` of
an op's provenance path (`.../executor.topk/...`; a bare substring would
also find `jit(executor_program)`), under the prefixes `STAGE_PREFIXES`.
An op counts to every stage on its path (`knn.gather` lies inside
`executor.match`). A `while` and the ops of its body are events of their
own, the first without provenance: it takes the stages its body's ops
share, and every number here is a union of intervals, so nothing counts
twice: a stage's seconds, `scoped_s` (the ops that name a stage) and
`all_s` (all ops: the device's busy time).

A trace without the span or without a scope (an older commit) reduces to
None, and so does every reader over it.

    python3 benchmark/launch_reduce.py <xplane.pb>     # the tables
"""

from __future__ import annotations

import bisect
import glob
import os
import re

import span_reduce
import xplane_scopes
from trace_reduce import (REQUEST, _union, events_of, short_module,
                          short_op)

DISPATCH, WAIT = "device.dispatch", "device.wait"
STAGE_PREFIXES = ("impact.", "rescore.", "executor.", "aggs.", "knn.")
# a stage as one component of a provenance path: bounded by `/`, by the
# brackets a transform wraps a name in, or by the path's ends
_STAGE = re.compile(
    r"(?:^|[/(])((?:" + "|".join(p[:-1] for p in STAGE_PREFIXES)
    + r")\.[\w.]+)(?=[/)]|$)")


def stages_of(provenance: str) -> list:
    """The stages on an op's provenance path, outermost first."""
    return _STAGE.findall(provenance)


# ---------------------------------------------------------------------
# the host half: dispatch, launch latency, read-back
# ---------------------------------------------------------------------

def _requests(lines: list) -> list:
    reqs = sorted((a, b) for evs in lines for n, a, b in evs if n == REQUEST)
    if reqs:
        return reqs
    # a trace that nothing annotated from outside: every top-level span
    return sorted((r.start, r.end) for evs in lines
                  for r in span_reduce.nest(evs))


def _idle_in(gaps: list, starts: list, lo: int, hi: int) -> int:
    """ns of the sorted idle intervals `gaps` inside [lo, hi]."""
    total, i = 0, max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(gaps) and gaps[i][0] < hi:
        total += max(min(gaps[i][1], hi) - max(gaps[i][0], lo), 0)
        i += 1
    return total


def seam(devices: dict, lines: list):
    """-> the reduction the module's docstring describes, or None where
    the trace holds no `device.dispatch` span or no device plane."""
    reqs = _requests(lines)
    if not reqs or not devices:
        return None
    req_starts = [a for a, _b in reqs]

    def request_of(t: int):
        i = bisect.bisect_right(req_starts, t) - 1
        return reqs[i] if i >= 0 and t < reqs[i][1] else None

    def inside(name: str) -> list:
        return sorted((a, b) for evs in lines for n, a, b in evs
                      if n == name and request_of(a))
    dispatches, waits = inside(DISPATCH), inside(WAIT)
    if not dispatches:
        return None
    # one stream: the first device plane (a launch over several chips is
    # one dispatch and a module event a chip; no cell has one yet)
    dev = devices[sorted(devices)[0]]
    modules = sorted((a, b, n) for n, a, b in dev["modules"]
                     if request_of(a))
    lo, hi = reqs[0][0], max(b for _a, b in reqs)
    gaps = span_reduce._idle({"": dev}, lo, hi)
    gap_starts = [a for a, _b in gaps]

    # launches and module events, in order: a module event that started
    # before the dispatch in hand did was launched by no span
    launch_of, j = {}, 0
    for d in dispatches:
        while j < len(modules) and modules[j][0] < d[0]:
            j += 1
        if j < len(modules):
            launch_of[j] = d
            j += 1
    d_starts = [a for a, _b in dispatches]
    m_starts = [a for a, _b, _n in modules]
    ended, latest = [], lo          # the latest end among modules[:i + 1]
    for _a, b, _n in modules:
        latest = max(latest, b)
        ended.append(latest)

    latency = gap = readback = idle = read = 0
    prev_wait_end = lo
    for w in waits:
        # the wait's region: from the first dispatch since the wait before
        # it (inside its own request) to its end
        region_lo = max(prev_wait_end, request_of(w[0])[0])
        k0 = bisect.bisect_left(d_starts, region_lo)
        k1 = bisect.bisect_left(d_starts, w[1])
        read += k1 - k0
        start = d_starts[k0] if k1 > k0 else w[0]
        i0 = bisect.bisect_left(m_starts, start)
        cursor = ended[i0 - 1] if i0 else lo    # the program before's end
        for i in range(i0, bisect.bisect_left(m_starts, w[1])):
            a, b, _n = modules[i]
            if b > w[1]:
                break           # still running at the wait's end: not read
            edge = max(cursor, start)
            d = launch_of.get(i)
            if d is not None and d[0] >= start:
                latency += max(a - max(d[0], edge), 0)
                gap += max(min(d[0], a) - edge, 0)
            else:
                gap += max(a - edge, 0)
            cursor = max(cursor, b)
        edge = min(max(cursor, start), w[1])
        gap += max(w[0] - edge, 0)
        readback += w[1] - max(w[0], edge)
        idle += _idle_in(gaps, gap_starts, start, w[1])
        prev_wait_end = w[1]
    parts = latency + gap + readback
    return {"requests": len(reqs), "dispatches": len(dispatches),
            "waits": len(waits), "modules": len(modules),
            "launches": len(launch_of),
            "unmatched_modules": [short_module(n)
                                  for i, (_a, _b, n) in enumerate(modules)
                                  if i not in launch_of],
            "unread_launches": len(dispatches) - read,
            "dispatch_s": sum(b - a for a, b in dispatches) / 1e9,
            "launch_latency_s": latency / 1e9, "gap_s": gap / 1e9,
            "readback_s": readback / 1e9, "idle_s": idle / 1e9,
            "identity_error": abs(parts - idle) / idle if idle else 0.0}


# ---------------------------------------------------------------------
# the device half: seconds by named stage
# ---------------------------------------------------------------------

PROVENANCE = "tf_op"     # the stat that holds an op's `jax` name stack


def _metadata(plane_parts: list) -> dict:
    """XEventMetadata id -> (name, the stages its provenance names). The
    provenance is the string stat named `tf_op` (XPlane.stat_metadata,
    field 5, names the stats); a plane that names no stat is read by every
    string stat."""
    stat_names = {}
    for n, w, entry in plane_parts:
        if n == 5 and w == 2:
            sid, name = 0, b""
            for k, kw, val in xplane_scopes.fields(entry):
                if k == 1 and kw == 0:
                    sid = val
                elif k == 2 and kw == 2:
                    name = next((bytes(v) for f, fw, v
                                 in xplane_scopes.fields(val)
                                 if f == 2 and fw == 2), b"")
            stat_names[sid] = name.decode("utf-8", "replace")
    out = {}
    for n, w, entry in plane_parts:
        if n != 4 or w != 2:
            continue
        for k, kw, val in xplane_scopes.fields(entry):
            if k != 2 or kw != 2:
                continue
            mid, name, found = 0, "", []
            for f, fw, v in xplane_scopes.fields(val):
                if f == 1 and fw == 0:
                    mid = v
                elif f == 2 and fw == 2:
                    name = bytes(v).decode("utf-8", "replace")
                elif f == 5 and fw == 2:            # an XStat
                    stat = {sn: sv for sn, sw, sv in xplane_scopes.fields(v)
                            if (sn, sw) in ((1, 0), (5, 2))}
                    if 5 in stat and (not stat_names or stat_names.get(
                            stat.get(1)) == PROVENANCE):
                        found += stages_of(
                            bytes(stat[5]).decode("utf-8", "replace"))
            out[mid] = (name, found)
    return out


def _inherit(events: list) -> None:
    """A `while` is an event without provenance whose body's ops are
    events of their own inside it: an op that names no stage takes the
    stages every op nested in it names (none where they disagree).
    `events`: [start, end, op, stages] of one line, edited in place."""
    events.sort(key=lambda e: (e[0], -e[1]))
    stack, inside = [], {}
    for i, (a, b, _op, found) in enumerate(events):
        while stack and a >= events[stack[-1]][1]:
            stack.pop()
        if found:
            for j in stack:
                if not events[j][3]:
                    inside.setdefault(j, []).append(set(found))
        stack.append(i)
    for j, sets in inside.items():
        events[j][3] = sorted(set.intersection(*sets))


def stages(path: str):
    """-> {"stage_s": {stage: seconds, the union of its ops' intervals},
    "scoped_s" / "all_s": the union of the ops that name a stage / of all
    ops (the device's busy time), "ops": [(short op name, summed seconds,
    its stages)] by seconds}, summed over the device planes; None where
    the file holds no device plane or no op names a stage."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    by_stage, ops, scoped, busy, planes = {}, {}, 0, 0, 0
    for num, wt, plane in xplane_scopes.fields(space):
        if num != 1 or wt != 2:
            continue
        parts = list(xplane_scopes.fields(plane))
        name = next((bytes(v) for n, w, v in parts if n == 2 and w == 2), b"")
        if not name.startswith(xplane_scopes.DEVICE_PLANE):
            continue
        planes += 1
        meta = _metadata(parts)
        for n, w, line in parts:
            if n != 3 or w != 2:
                continue
            lparts = list(xplane_scopes.fields(line))
            if not any(f == 2 and fw == 2
                       and bytes(v) == xplane_scopes.OPS_LINE
                       for f, fw, v in lparts):
                continue
            events = []
            for f, fw, ev in lparts:
                if f != 4 or fw != 2:
                    continue
                mid = off = dur = 0
                for e, ew, v in xplane_scopes.fields(ev):
                    if ew != 0:
                        continue
                    if e == 1:
                        mid = v
                    elif e == 2:
                        off = v
                    elif e == 3:
                        dur = v
                op, found = meta.get(mid, ("", []))
                events.append([off, off + dur, op, list(found)])
            _inherit(events)
            busy += sum(b - a for a, b in _union(
                [(a, b) for a, b, _op, _f in events]))
            scoped += sum(b - a for a, b in _union(
                [(a, b) for a, b, _op, found in events if found]))
            for a, b, op, found in events:
                for stage in set(found):
                    by_stage.setdefault((planes, stage), []).append((a, b))
                row = ops.setdefault(short_op(op), [0, found])
                row[0] += b - a
    if not planes or not scoped:
        return None
    stage_s = {}
    for (_plane, stage), spans in by_stage.items():
        stage_s[stage] = stage_s.get(stage, 0.0) + sum(
            b - a for a, b in _union(spans)) / 1e12
    return {"stage_s": stage_s, "scoped_s": scoped / 1e12,
            "all_s": busy / 1e12,
            "ops": sorted(((n, ps / 1e12, found)
                           for n, (ps, found) in ops.items()),
                          key=lambda r: -r[1])}


# ---------------------------------------------------------------------
# for the per-layer readers
# ---------------------------------------------------------------------

def seam_file(path: str):
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    devices = events_of(profile)[0]
    return seam(devices, span_reduce.host_lines(profile))


_memo: dict = {}


def _newest(ctx: dict):
    found = glob.glob(os.path.join(span_reduce.OUT_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not found or not ctx.get("trace"):
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _memo:
        _memo.clear()
        _memo[key] = {}
    return path, _memo[key]


def seam_for_ctx(ctx: dict):
    """`seam` of this run's trace (the newest under `benchmark_out/`, as
    `span_reduce.for_ctx` takes it), held to the run's own count of traced
    requests; parsed once a process."""
    got = _newest(ctx)
    if got is None:
        return None
    path, memo = got
    if "seam" not in memo:
        memo["seam"] = seam_file(path)
    out = memo["seam"]
    if out is None or out["requests"] != ctx["trace"]["requests"]:
        return None
    return out


def stages_for_ctx(ctx: dict):
    got = _newest(ctx)
    if got is None:
        return None
    path, memo = got
    if "stages" not in memo:
        try:
            memo["stages"] = stages(path)
        except (ValueError, IndexError, OSError):
            memo["stages"] = None
    return memo["stages"]


def seam_ms_per_query(ctx: dict, key: str):
    out = seam_for_ctx(ctx)
    if out is None or not ctx["trace"]["queries"]:
        return None
    return 1e3 * out[key] / ctx["trace"]["queries"]


def stage_ms_per_query(ctx: dict, *names: str):
    """Device ms a traced query under the stages `names`, summed; None
    where the trace names none of them."""
    out = stages_for_ctx(ctx)
    if out is None or not ctx["trace"]["queries"]:
        return None
    have = [out["stage_s"][n] for n in names if n in out["stage_s"]]
    if not have:
        return None
    return 1e3 * sum(have) / ctx["trace"]["queries"]


def tables(path: str) -> str:
    rows = []
    s = seam_file(path)
    if s is None:
        rows.append("the trace holds no device.dispatch span")
    else:
        n = s["requests"]
        rows += [f"{n} requests: {s['dispatches']} device.dispatch, "
                 f"{s['modules']} XLA Modules events inside requests "
                 f"({s['launches']} matched), {s['waits']} device.wait, "
                 f"{s['unread_launches']} launches no wait read",
                 "module events no dispatch span launched: "
                 + (", ".join(sorted(set(s["unmatched_modules"]))) or "none"),
                 "", f"{'':22}{'s':>12}{'ms / request':>14}"]
        for key in ("dispatch_s", "launch_latency_s", "gap_s", "readback_s",
                    "idle_s"):
            rows.append(f"{key:22}{s[key]:12.6f}{1e3 * s[key] / n:14.4f}")
        rows.append(f"launch latency + gaps + read-back against the idle "
                    f"time of the same regions: off by "
                    f"{100 * s['identity_error']:.3f}%")
    st = stages(path)
    rows.append("")
    if st is None:
        rows.append("no device op names a stage")
        return "\n".join(rows)
    rows += [f"device busy under a named stage: {st['scoped_s']:.6f} s of "
             f"{st['all_s']:.6f} s ({100 * st['scoped_s'] / st['all_s']:.2f}"
             f"%)", "", f"{'stage':28}{'s (union)':>12}"]
    for stage, sec in sorted(st["stage_s"].items(), key=lambda kv: -kv[1]):
        rows.append(f"{stage:28}{sec:12.6f}")
    rows += ["", f"{'op':58}{'s (sum)':>10}  stages"]
    for name, sec, found in st["ops"][:25]:
        rows.append(f"{name[:56]:58}{sec:10.6f}  "
                    + ("/".join(found) or "-- none --"))
    return "\n".join(rows)


if __name__ == "__main__":
    import sys
    print(tables(sys.argv[1]))
