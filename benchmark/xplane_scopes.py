"""Device time by named scope, read from an `.xplane.pb` itself.

`jax.profiler.ProfileData` gives an `XLA Ops` event its name (the HLO line)
and its own stats (offset, duration); the op's provenance
(`jit(executor_program)/jit(main)/aggs.bucketed_sub/scatter-add`, with every
`jax.named_scope` on the way) is a stat of the event's *metadata*, which
that reader does not hand out. So this walks the protobuf's wire format
(tensorflow/tsl `xplane.proto`; field numbers below) with nothing but the
standard library: XSpace.planes=1; XPlane.name=2, lines=3,
event_metadata=4 (a map: key=1, value=2); XLine.name=2, events=4;
XEvent.metadata_id=1, duration_ps=3; XEventMetadata.id=1, name=2, stats=5;
XStat.str_value=5.

`scope_seconds(path, scope)` -> (seconds of the `XLA Ops` events whose
metadata names `scope` in a string stat, seconds of all of them), summed
over the device planes; None where the file holds no device plane."""

from __future__ import annotations

DEVICE_PLANE = b"/device:TPU:"
OPS_LINE = b"XLA Ops"


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: a varint's value,
    or the bytes of a length-delimited / fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i: i + size], i + size
        elif wt == 1:
            val, i = buf[i: i + 8], i + 8
        elif wt == 5:
            val, i = buf[i: i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val


def _names_scope(meta, scope: bytes) -> bool:
    for num, wt, val in fields(meta):
        if num == 5 and wt == 2:                        # an XStat
            if any(n == 5 and w == 2 and scope in bytes(v)
                   for n, w, v in fields(val)):
                return True
    return False


def scope_seconds(path: str, scope: str):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    needle, scoped_ps, all_ps, planes = scope.encode(), 0, 0, 0
    for num, wt, plane in fields(space):
        if num != 1 or wt != 2:
            continue
        parts = list(fields(plane))
        name = next((bytes(v) for n, w, v in parts if n == 2 and w == 2), b"")
        if not name.startswith(DEVICE_PLANE):
            continue
        planes += 1
        in_scope = set()
        for n, w, entry in parts:
            if n != 4 or w != 2:
                continue
            for k, kw, val in fields(entry):
                if k == 2 and kw == 2 and _names_scope(val, needle):
                    in_scope.add(next(v for f, fw, v in fields(val)
                                      if f == 1 and fw == 0))
        for n, w, line in parts:
            if n != 3 or w != 2:
                continue
            lparts = list(fields(line))
            if not any(f == 2 and fw == 2 and bytes(v) == OPS_LINE
                       for f, fw, v in lparts):
                continue
            for f, fw, ev in lparts:
                if f != 4 or fw != 2:
                    continue
                mid = dur = 0
                for e, ew, v in fields(ev):
                    if ew == 0 and e == 1:
                        mid = v
                    elif ew == 0 and e == 3:
                        dur = v
                all_ps += dur
                if mid in in_scope:
                    scoped_ps += dur
    if not planes:
        return None
    return scoped_ps / 1e12, all_ps / 1e12
