"""What the Terms Aggregation operations of `big5` have to read, and how
long the device took over them.

`request_bytes(shape, rows)`: the bytes one operation's group-by has to move
from HBM, from the configuration's shapes alone, whatever the program then
does: `rows` padded rows times four bytes for every plane the request
names. A `terms` or a keyword `cardinality` names its field's ordinals,
laid out by value with the document of each value beside them (the column's
own layout: two planes), and the query's mask; a `multi_terms` or a
`composite` names one ordinal plane a source and the mask. Reading a
combined plane instead of the sources', counting a window's rows alone, a
second pass over gathered rows, the scatter's read-modify-write of its
buckets: all of that is the program's form, and moves the time, not this
count. A group-by does an add a four-byte element, so it is bound by
memory: bytes over `peaks.json`'s `hbm_bytes_per_s` is the least time it
could take, and that over the device's time in the `aggs.terms` stage its
share of the roofline (`terms_stage_hbm_roofline_share`).

`query_bytes(ctx)`: the mean over the seven operations (the pool is whole
rotations, a seventh of the requests each) times the launches a query the
window's counters show; None where the program has no such counters."""

from __future__ import annotations

import json
import os

import big5_reference as reference

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                      "osb-big5-1shard.json")
STAGE = "aggs.terms"


def planes(shape: str) -> int:
    """Four-byte planes operation `shape` has to read a row."""
    _name, kind, fields, _size = reference.AGGS[shape]
    if kind in ("terms", "cardinality"):
        return 3                # ordinals, document of value, mask
    return len(fields) + 1      # an ordinal plane a source, mask


def request_bytes(shape: str, rows: int) -> float:
    return 4.0 * rows * planes(shape)


def padded_rows(ndocs: int) -> int:
    """A segment's rows as the device holds them: the next power of two."""
    return max(1 << (int(ndocs) - 1).bit_length(), 16)


def query_bytes(ctx):
    c = ctx["window"]["counters"]
    launches, queries = c.get("executor.launches"), ctx["window"]["queries"]
    if not launches or not queries or "aggs.terms.ordinals" not in c:
        return None
    with open(CONFIG) as f:
        rows = padded_rows(json.load(f)["ndocs"])
    mean = sum(request_bytes(s, rows) for s in reference.SHAPES) \
        / len(reference.SHAPES)
    return mean * launches / queries
