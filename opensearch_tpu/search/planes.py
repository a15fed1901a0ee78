"""Per-segment derived planes: what one is, what its ids mean and how long
it stays resident (bucket-id, rank, combination and nested-sort planes, in
the HBM ledger), with the calendar and auto-interval arithmetic of those ids.

Lowest of the five modules `compiler.py` pictures: imports `index/`,
`ops/aggs`, `obs/hbm_ledger` and `utils/metrics`; of `search/` only
`query_dsl`'s error type, and `plan.ShardContext` as an annotation.
"""

from __future__ import annotations

import re
from bisect import bisect_left
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..index.segment import Segment, next_pow2, rows_in_order
from ..ops import aggs as agg_ops
from ..utils.metrics import METRICS, CounterGroup
from . import query_dsl as dsl

if TYPE_CHECKING:
    from .plan import ShardContext

# the per-segment planes that stay on the device so that a launch is handed
# none of `ndocs_pad` elements: a date_histogram's bucket ids and a field
# sort's ranks (builds / hits of the per-segment caches, bytes built)
BUCKET_PLANE_STATS = CounterGroup(METRICS, "aggs.bucket_plane",
                                  {"builds": 0, "hits": 0, "bytes": 0})
RANK_PLANE_STATS = CounterGroup(METRICS, "sort.rank_plane",
                                {"builds": 0, "hits": 0, "bytes": 0})
# what the block join costs. Counted where a `nested` node is bound to a
# segment (`compiler.prepare`, once a launch): `queries` the nodes,
# `child_rows` the padded slots of the child space the child clause and
# the join read, `child_rows_real` the children among them, `parents` the
# padded parent rows the join writes, `join_updates` the updates its
# scatters take (a slot a scatter: two for `avg` / `sum` / `max` / `min`,
# one for `none`). `sort_plane_builds`: the nested sort keys built
# (`nested_sort_plane`: 0 once a (field, path, mode) is resident).
# `inner_hits_requests` / `_child_rows` / `_readback_bytes`: the inner-hits
# launches (`executor._nested_inner_hits`, one a request, nested clause
# and segment), the child rows they gather (the blocks of a page's
# parents, padded) and the bytes read back for them. `programs`: the
# distinct programs with a `nested` node compiled so far
NESTED_STATS = CounterGroup(METRICS, "nested", {
    "queries": 0, "child_rows": 0, "child_rows_real": 0, "parents": 0,
    "join_updates": 0, "sort_plane_builds": 0, "inner_hits_requests": 0,
    "inner_hits_child_rows": 0, "inner_hits_readback_bytes": 0,
    "programs": 0})


def segment_plane(seg: Segment, cache_name: str, key, kind: str, stats,
                  build: Callable[[], tuple]) -> tuple:
    """One i32[ndocs_pad] plane of per-document ids (-1 = none) kept on the
    device for the segment's lifetime, with whatever `build` returns after
    its host ids (a numpy array among it goes to the device and is charged
    with the plane): -> (device plane, *rest). Cached under
    `seg.<cache_name>[key]` (a tuple that starts with the field, or with the
    tuple of the fields of a plane over several) and attributed in the HBM
    ledger as `kind`;
    `derived._purge_query_caches` drops a rematerialized field's planes
    and the segment's GC the rest. `stats` counts builds, hits and bytes.
    The per-segment lock keeps two first requests from building (and
    charging) one plane twice."""
    cache = seg.__dict__.setdefault(cache_name, {})
    hit = cache.get(key)
    if hit is not None:
        stats.inc("hits")
        return hit
    lock = seg.__dict__.setdefault("_plane_build_lock",
                                   __import__("threading").Lock())
    with lock:
        hit = cache.get(key)
        if hit is not None:
            stats.inc("hits")
            return hit
        import jax.numpy as jnp

        from ..obs.hbm_ledger import LEDGER
        ids, *rest = build()
        pad = np.full(seg.ndocs_pad, -1, dtype=np.int32)
        pad[: len(ids)] = ids
        plane = jnp.asarray(pad)
        nbytes = pad.nbytes + sum(x.nbytes for x in rest
                                  if isinstance(x, np.ndarray))
        rest = [jnp.asarray(x) if isinstance(x, np.ndarray) else x
                for x in rest]
        alloc = LEDGER.register(kind, nbytes, owner=seg, segment=seg,
                                label=f"{kind}[{seg.name}][{key}]")
        seg.__dict__.setdefault("_plane_allocs", {})[cache_name, key] = alloc
        stats.inc("builds")
        stats.inc("bytes", nbytes)
        cache[key] = (plane, *rest)
        return cache[key]


def _nested_column(seg: Segment, field: str, path: str):
    """(block, child numeric column) of `path`.`field`, or (None, None)."""
    blk = seg.nested.get(path)
    col = blk.child.numeric_cols.get(field) if blk is not None else None
    return (blk, col) if col is not None else (None, None)


def _run_reduce(values: np.ndarray, keep: np.ndarray, parent: np.ndarray,
                mode: str):
    """`mode` (min / max / sum / avg) of the kept `values` over the runs of
    the nondecreasing `parent`: -> (parents that keep a value, ascending;
    their aggregate, f64). A segmented reduction (`ufunc.reduceat` at the
    runs' first rows), no scatter."""
    p, v = parent[keep], values[keep].astype(np.float64)
    if not len(p):
        return p, v
    first = np.flatnonzero(np.concatenate(([True], p[1:] != p[:-1])))
    if mode == "min":
        out = np.minimum.reduceat(v, first)
    elif mode == "max":
        out = np.maximum.reduceat(v, first)
    else:                              # sum / avg
        out = np.add.reduceat(v, first)
        if mode == "avg":
            out = out / np.diff(first, append=len(p))
    return p[first], out


def nested_sort_plane(seg: Segment, field: str, path: str, mode: str):
    """The resident key of a nested sort (reference NestedSortBuilder): an
    i32[ndocs_pad] plane on the device that holds, a parent, the rank of
    its `mode` (min / max / sum / avg) over its block's live children's
    `path`.`field` among the segment's distinct aggregates, -1 where no
    child has a value. Built once a (segment, field, path, mode) through
    `segment_plane` (the cache and the ledger's `nested_sort` category; a
    request carries nothing of `ndocs_pad`), dropped by
    `drop_segment_planes` or with the segment. None where the segment has
    no such child column."""
    blk, col = _nested_column(seg, field, path)
    if col is None:
        return None

    def build():
        NESTED_STATS.inc("sort_plane_builds")
        n = blk.child.ndocs
        parents, agg = _run_reduce(
            col.values[:n], col.present[:n] & blk.child.live[:n],
            blk.parent_of[:n], mode)
        ords = np.full(seg.ndocs, -1, np.int32)
        ords[parents] = np.searchsorted(np.unique(agg), agg)
        return (ords,)
    return segment_plane(seg, "_sort_dev_cache", (field, path, mode),
                         "nested_sort", RANK_PLANE_STATS, build)[0]


def nested_sort_value(seg: Segment, field: str, path: str, mode: str,
                      doc: int) -> Optional[float]:
    """What `nested_sort_plane` ranks, of one parent: the aggregate over
    its own block (a hit's sort value is read from the few rows of its
    block, not from a column over every parent), None where no live child
    has a value."""
    blk, col = _nested_column(seg, field, path)
    if col is None:
        return None
    a, b = blk.children_of(doc)
    keep = col.present[a:b] & blk.child.live[a:b]
    _p, agg = _run_reduce(col.values[a:b], keep,
                          np.zeros(b - a, np.int32), mode)
    return float(agg[0]) if len(agg) else None


def drop_segment_planes(seg: Segment, field: str) -> None:
    """Drop `field`'s rank, bucket and combination planes (a combination
    plane is every one of its fields') and release their ledger bytes (a
    rematerialized derived field: `derived._purge_query_caches`)."""
    from ..obs.hbm_ledger import LEDGER
    allocs = seg.__dict__.get("_plane_allocs", {})
    for cache_name in ("_sort_dev_cache", "_date_bucket_cache",
                       "_combo_plane_cache"):
        cache = seg.__dict__.get(cache_name, {})
        for key in [k for k in cache if field == k[0] or (
                isinstance(k[0], tuple) and field in k[0])]:
            del cache[key]
            LEDGER.release(allocs.pop((cache_name, key), None))
    # the host-side state that names the field: the mesh path's copies of
    # a `multi_terms` space, the multi-valued flag
    mesh = seg.__dict__.get("_multi_terms_cache", {})
    for fields in [k for k in mesh if field in k]:
        del mesh[fields]
    seg.__dict__.get("_kw_multi_cache", {}).pop(field, None)


def date_bucket_plane(seg: Segment, field: str, interval_ms: int,
                      offset_ms: int, calendar: Optional[str]):
    """Exact date bucketing on host i64, once per (segment, field, interval,
    offset, calendar), then resident: -> (bucket ids i32[ndocs_pad] on the
    device, -1 = no value, min_bucket, nbuckets, starts). Calendar intervals
    follow real calendars (reference Rounding.Builder). `starts` is
    `run_starts` of the ids, on the device too, where the segment's values
    are in row order (an append-only log), else None."""
    def build():
        ids, mn, nb = date_bucket_ids(seg, field, interval_ms, offset_ms,
                                      calendar)
        return ids, mn, nb, run_starts(ids, nb, seg.ndocs_pad)
    return segment_plane(seg, "_date_bucket_cache",
                         (field, interval_ms, offset_ms, calendar),
                         "agg_bucket_plane", BUCKET_PLANE_STATS, build)


def date_bucket_ids(seg: Segment, field: str, interval_ms: int,
                    offset_ms: int, calendar: Optional[str]):
    """(bucket ids i32[ndocs] from the least bucket, -1 = no value, the
    least bucket, the number of buckets) of a date column, on host i64."""
    col = seg.numeric_cols.get(field)
    if col is None or not col.present.any():
        return np.full(seg.ndocs, -1, np.int32), 0, 1
    vals = col.values.astype(np.int64)
    if calendar is None:
        b = np.floor_divide(vals - offset_ms, interval_ms)
    else:
        b = calendar_bucket_ids(vals, calendar)
    bp = b[col.present]
    mn, mx = int(bp.min()), int(bp.max())
    ids = np.where(col.present, b - mn, -1).astype(np.int32)
    return ids, mn, int(mx - mn + 1)


def run_starts(ids: np.ndarray, nbuckets: int,
               ndocs_pad: int) -> Optional[np.ndarray]:
    """i32[nbuckets + 1] for `ops.aggs.run_counts`: `starts[b]` is the first
    row whose id, or the id of the nearest row before it that has one, is
    at least b, so `starts[nbuckets]` = `len(ids)`. None where the ids of
    the rows that have a value (id >= 0; the others weigh nothing) are not
    non-decreasing in row order (`rows_in_order`), or `run_blocks` has no
    cut for the sizes: such a plane is counted by scatter-add."""
    if agg_ops.run_blocks(ndocs_pad, nbuckets + 1) is None:
        return None
    filled = rows_in_order(ids, ids >= 0)
    if filled is None:
        return None
    return np.searchsorted(filled, np.arange(nbuckets + 1),
                           side="left").astype(np.int32)


_DAY_MS = 86400000


def calendar_bucket_ids(ms: np.ndarray, calendar: str) -> np.ndarray:
    """Calendar bucket ids of epoch-millisecond values (UTC), as whole
    columns: fixed-length units by floor division, months and years by
    numpy's proleptic Gregorian `datetime64`."""
    ms = np.asarray(ms, dtype=np.int64)
    if calendar in ("minute", "1m"):
        return ms // 60000
    if calendar in ("hour", "1h"):
        return ms // 3600000
    if calendar in ("day", "1d"):
        return ms // _DAY_MS
    if calendar in ("week", "1w"):
        return (ms // _DAY_MS + 3) // 7     # epoch day 0 = Thursday
    if calendar in ("year", "1y"):
        return ms.astype("datetime64[ms]").astype(
            "datetime64[Y]").astype(np.int64)
    months = ms.astype("datetime64[ms]").astype(
        "datetime64[M]").astype(np.int64)   # since 1970-01
    if calendar in ("month", "1M"):
        return months
    if calendar in ("quarter", "1q"):
        return months // 3
    raise ValueError(f"unknown calendar_interval [{calendar}]")


def calendar_bucket_start_ms(b: int, calendar: str) -> int:
    """Epoch ms (UTC) at which calendar bucket `b` starts: the inverse of
    `calendar_bucket_ids`."""
    if calendar in ("minute", "1m"):
        return b * 60000
    if calendar in ("hour", "1h"):
        return b * 3600000
    if calendar in ("day", "1d"):
        return b * _DAY_MS
    if calendar in ("week", "1w"):
        return (b * 7 - 3) * _DAY_MS
    months = {"month": 1, "1M": 1, "quarter": 3, "1q": 3, "year": 12,
              "1y": 12}.get(calendar)
    if months is None:
        raise ValueError(f"unknown calendar_interval [{calendar}]")
    return int(np.datetime64(b * months, "M").astype(
        "datetime64[ms]").astype(np.int64))


_CAL_MS = {"month": None, "1M": None, "year": None, "1y": None, "quarter": None,
           "1q": None, "week": None, "1w": None}

FIXED_MS = {"ms": 1, "s": 1000, "m": 60000, "h": 3600000, "d": 86400000}


def parse_interval_ms(s, allow_negative: bool = False) -> int:
    if isinstance(s, (int, float)):
        return int(s)
    # sign is legal only where the caller says so (date_histogram `offset`
    # accepts "+6h"/"-3h"; a negative fixed_interval must stay an error)
    sign_re = r"([+-]?)" if allow_negative else r"()"
    mm = re.fullmatch(sign_re + r"(\d+)(ms|s|m|h|d)", str(s))
    if not mm:
        raise ValueError(f"invalid fixed_interval [{s}]")
    v = int(mm.group(2)) * FIXED_MS[mm.group(3)]
    return -v if mm.group(1) == "-" else v


def crc32_vocab_hashes(vocab, pad: int) -> np.ndarray:
    """crc32 of each vocab string, zero-padded to `pad` — the HLL value
    hashes; shared by the host segment path and the mesh service so the
    two register sets merge bit-identically."""
    import zlib
    out = np.zeros(pad, dtype=np.uint32)
    out[: len(vocab)] = np.fromiter(
        (zlib.crc32(v.encode()) for v in vocab), np.uint32,
        count=len(vocab))
    return out


def kw_hash_cache(seg: Segment, field: str) -> np.ndarray:
    cache = getattr(seg, "_kw_hash_cache", None)
    if cache is None:
        cache = seg._kw_hash_cache = {}
    if field not in cache:
        col = seg.keyword_cols[field]
        cache[field] = crc32_vocab_hashes(
            col.vocab, next_pow2(max(len(col.vocab), 1)))
    return cache[field]


_B32 = "0123456789bcdefghjkmnpqrstuvwxyz"


def _geohash_strings(codes: np.ndarray, precision: int) -> List[str]:
    out = []
    for c in codes.tolist():
        s = []
        for i in range(precision):
            shift = 5 * (precision - 1 - i)
            s.append(_B32[(c >> shift) & 31])
        out.append("".join(s))
    return out


def geo_grid_cache(seg: Segment, field: str, kind: str, precision: int):
    """(vocab cell keys, per-doc cell ordinal i32[ndocs_pad], -1 missing) —
    computed once per (segment, field, kind, precision) on the host; the
    device then bincounts ordinals exactly like the terms agg. (Reference
    GeoHashGridAggregator/GeoTileGridAggregator bucket by cell the same way,
    via doc-value cell ids.)"""
    cache = getattr(seg, "_geo_grid_cells", None)
    if cache is None:
        cache = seg._geo_grid_cells = {}
    key = (field, kind, precision)
    if key in cache:
        return cache[key]
    col = seg.geo_cols.get(field)
    ords = np.full(seg.ndocs_pad, -1, np.int32)
    vocab: List[str] = []
    if col is not None and col.present.any():
        lat = col.lat[: seg.ndocs].astype(np.float64)
        lon = col.lon[: seg.ndocs].astype(np.float64)
        if kind == "geotile_grid":
            z = precision
            n = 1 << z
            x = np.clip(np.floor((lon + 180.0) / 360.0 * n), 0, n - 1)
            latc = np.clip(lat, -85.05112878, 85.05112878)
            latr = np.deg2rad(latc)
            y = np.clip(np.floor(
                (1.0 - np.log(np.tan(latr) + 1.0 / np.cos(latr)) / np.pi)
                / 2.0 * n), 0, n - 1)
            codes = (x.astype(np.int64) * n + y.astype(np.int64))
            uniq, inv = np.unique(codes, return_inverse=True)
            vocab = [f"{z}/{int(c) // n}/{int(c) % n}" for c in uniq]
        else:  # geohash
            nbits = 5 * precision
            lonb = (nbits + 1) // 2
            latb = nbits // 2
            li = np.clip(np.floor((lon + 180.0) / 360.0 * (1 << lonb)),
                         0, (1 << lonb) - 1).astype(np.uint64)
            la = np.clip(np.floor((lat + 90.0) / 180.0 * (1 << latb)),
                         0, (1 << latb) - 1).astype(np.uint64)
            codes = np.zeros(len(lat), np.uint64)
            # interleave, lon first (standard geohash bit order)
            for b in range(nbits):
                if b % 2 == 0:
                    src, idx = li, lonb - 1 - b // 2
                else:
                    src, idx = la, latb - 1 - b // 2
                bit = (src >> np.uint64(idx)) & np.uint64(1)
                codes = (codes << np.uint64(1)) | bit
            uniq, inv = np.unique(codes, return_inverse=True)
            vocab = _geohash_strings(uniq, precision)
        o = np.where(col.present[: seg.ndocs], inv.astype(np.int32), -1)
        ords[: seg.ndocs] = o
    cache[key] = (vocab, ords)
    return cache[key]


# auto_date_histogram's roundings (reference AutoDateHistogramAggregation-
# Builder.buildRoundings, recalled): a unit and the multiples of it a bucket
# may span. (abbreviation, `date_bucket_plane` calendar, inner intervals)
AUTO_ROUNDINGS = (
    ("s", None, (1, 5, 10, 30)),
    ("m", "minute", (1, 5, 10, 30)),
    ("h", "hour", (1, 3, 12)),
    ("d", "day", (1, 7)),
    ("M", "month", (1, 3)),
    ("y", "year", (1, 5, 10, 20, 50, 100)),
)


def auto_unit_ids(ms, unit: int) -> np.ndarray:
    """Bucket ids of epoch-millisecond values under rounding `unit` (UTC):
    whole seconds, minutes, hours and days since the epoch, calendar months
    since 1970-01, years since 1970."""
    cal = AUTO_ROUNDINGS[unit][1]
    ms = np.asarray(ms, dtype=np.int64)
    return ms // 1000 if cal is None else calendar_bucket_ids(ms, cal)


def auto_unit_start_ms(bucket_id: int, unit: int) -> int:
    """Epoch ms at which bucket `bucket_id` of rounding `unit` starts."""
    cal = AUTO_ROUNDINGS[unit][1]
    return (int(bucket_id) * 1000 if cal is None
            else calendar_bucket_start_ms(int(bucket_id), cal))


def auto_unit_for(lo_ms: int, hi_ms: int, target: int) -> int:
    """The finest rounding under which the buckets from `lo_ms`'s to
    `hi_ms`'s, merged by the rounding's widest inner interval, number at
    most `target` (the coarsest where none does)."""
    for unit, (_abbr, _cal, inners) in enumerate(AUTO_ROUNDINGS):
        lo, hi = auto_unit_ids([lo_ms, hi_ms], unit)
        if -(-(int(hi) - int(lo) + 1) // inners[-1]) <= target:
            return unit
    return len(AUTO_ROUNDINGS) - 1


def auto_window(unit: int, target: int) -> int:
    """Buckets of rounding `unit` a launch counts: what `auto_unit_for`
    admits, as a power of two (a static size of the program)."""
    return next_pow2(target * AUTO_ROUNDINGS[unit][2][-1])


def auto_inner_for(nbuckets: int, unit: int, target: int) -> Optional[int]:
    """The least inner interval of rounding `unit` that merges `nbuckets`
    consecutive buckets into at most `target`; None where none does and a
    coarser rounding is left to try (the coarsest takes its widest)."""
    inners = AUTO_ROUNDINGS[unit][2]
    for inner in inners:
        if -(-nbuckets // inner) <= target:
            return inner
    return inners[-1] if unit + 1 == len(AUTO_ROUNDINGS) else None


def auto_bucket_end_ms(key_ms: int, interval: str) -> int:
    """Epoch ms at which the bucket that starts at `key_ms` ends, `interval`
    as the response names it (`7d`, `3M`)."""
    unit = next(u for u, r in enumerate(AUTO_ROUNDINGS)
                if r[0] == interval[-1])
    first = int(auto_unit_ids(key_ms, unit))
    return auto_unit_start_ms(first + int(interval[:-1]), unit)


# a combination space is enumerated through a table over the product of its
# sources' value spaces up to this many slots (a byte and an int32 each for
# the build's moment), beyond that by a sort of the rows' codes
_COMBO_TABLE_MAX = 1 << 26


class ComboSpace:
    """The combinations of source values that occur among a segment's
    documents, numbered in key order under each source's `order`: what a
    `multi_terms` or a `composite` over several sources counts into, one
    slot a combination that occurs (the product of the sources' value
    spaces, most of it empty, is laid out nowhere). `codes` i64[n]
    ascending: a combination's code is its sources' positions in mixed
    radix, first source first, a position being the value's ordinal under
    `asc` and `radix - 1 - ordinal` under `desc`. `sources` says how a
    source's ordinal decodes: ("terms", sorted values), ("hist", least
    bucket, interval) or ("date", least bucket, interval ms, calendar).
    A sequence of the key tuples besides (`len`, `[j]`, iteration), each
    decoded when asked for: a response names a page of them."""

    __slots__ = ("codes", "radix", "desc", "sources")

    def __init__(self, codes, radix, desc, sources):
        self.codes, self.radix = codes, tuple(radix)
        self.desc, self.sources = tuple(desc), tuple(sources)

    def __len__(self) -> int:
        return len(self.codes)

    def __iter__(self):
        return (self[j] for j in range(len(self.codes)))

    def __getitem__(self, j) -> tuple:
        rem, ords = int(self.codes[j]), []
        for n, desc in zip(reversed(self.radix), reversed(self.desc)):
            rem, t = divmod(rem, n)
            ords.append(n - 1 - t if desc else t)
        return tuple(self._value(src, o)
                     for src, o in zip(self.sources, reversed(ords)))

    @staticmethod
    def _value(src: tuple, o: int):
        if src[0] == "terms":
            return src[1][o]
        if src[0] == "hist":
            return (src[1] + o) * src[2]
        _, min_b, interval_ms, calendar = src
        if calendar:
            return calendar_bucket_start_ms(min_b + o, calendar)
        return int((min_b + o) * interval_ms)

    @staticmethod
    def _position(src: tuple, n: int, v) -> Tuple[int, bool]:
        """(how many of the source's `n` values lie under `v`, whether `v`
        is one of them)."""
        if src[0] == "terms":
            at = bisect_left(src[1], v)
            return at, at < n and src[1][at] == v
        if src[0] == "date" and src[3]:
            b = int(calendar_bucket_ids(np.asarray([int(v)]), src[3])[0])
            held = calendar_bucket_start_ms(b, src[3]) == int(v)
            return min(max(b - src[1] + (not held), 0), n), \
                held and 0 <= b - src[1] < n
        q = float(v) / src[2] - src[1]
        near = int(np.floor(q + 0.5))    # the bucket a key would name
        if 0 <= near < n and abs(ComboSpace._value(src, near) - v) \
                <= 1e-9 * max(1.0, abs(float(v))):
            return near, True
        return min(max(int(np.ceil(q)), 0), n), False

    def first_after(self, after: tuple) -> int:
        """The number of the first combination whose key comes after the
        key tuple `after` in the sources' orders (`len(self)`: none)."""
        code, mult = 0, [1]
        for n in reversed(self.radix[1:]):
            mult.insert(0, mult[0] * n)
        for src, n, desc, m, v in zip(self.sources, self.radix, self.desc,
                                      mult, after):
            under, held = self._position(src, n, v)
            # the first position whose value is `v` or comes after it
            at = (n - 1 - under if held else n - under) if desc else under
            code += at * m
            if not held:
                return int(np.searchsorted(self.codes, code, side="left"))
        return int(np.searchsorted(self.codes, code, side="right"))


def _combo_ids(per_source: list, desc: tuple, ndocs: int):
    """(combination numbers i32[ndocs], -1 = a document that lacks a
    source; codes i64[n] ascending) from each source's (ordinals i32[ndocs]
    with -1 = none, number of values)."""
    valid = np.ones(ndocs, bool)
    code = np.zeros(ndocs, np.int64)
    product = 1
    for (ords, n), d in zip(per_source, desc):
        n = max(int(n), 1)
        valid &= ords >= 0
        code *= n
        code += np.maximum((n - 1 - ords) if d else ords, 0)
        product *= n
    if product >= 1 << 62:
        raise dsl.QueryParseError(
            f"the sources' value spaces multiply to {product}: too many")
    held = code[valid]
    if product <= _COMBO_TABLE_MAX:
        seen = np.zeros(product, bool)
        seen[held] = True
        codes = np.flatnonzero(seen)
        number = (np.cumsum(seen, dtype=np.int32) - 1)[held]
    else:
        codes, number = np.unique(held, return_inverse=True)
    ids = np.full(ndocs, -1, np.int32)
    ids[valid] = number
    return ids, codes.astype(np.int64)


def _multi_terms_sources(seg: Segment, ctx: ShardContext,
                         fields: Tuple[str, ...]):
    """[(ordinals i32[ndocs], number of values)] and the `ComboSpace`
    sources of a `multi_terms` source list: a keyword's least ordinal, a
    numeric column's rank among its distinct values; a field the segment
    lacks excludes every document."""
    per_source, sources = [], []
    for f in fields:
        f = ctx.mappings.aliases.get(f, f)
        kcol = seg.keyword_cols.get(f)
        ncol = seg.numeric_cols.get(f)
        if kcol is not None:
            ords, values = kcol.min_ord[: seg.ndocs], kcol.vocab
        elif ncol is not None:
            ords = ncol.sort_ords()[: seg.ndocs]
            values = np.unique(ncol.values[ncol.present]).tolist()
        else:
            ords, values = np.full(seg.ndocs, -1, np.int32), []
        per_source.append((ords, len(values)))
        sources.append(("terms", values))
    return per_source, sources


def combo_space(per_source: list, sources: list, desc: tuple, ndocs: int):
    """(combination numbers i32[ndocs], `ComboSpace`) of per-source
    (ordinals, number of values) pairs and their decoders."""
    ids, codes = _combo_ids(per_source, desc, ndocs)
    return ids, ComboSpace(codes, [max(n, 1) for _o, n in per_source], desc,
                           sources)


def combo_plane(seg: Segment, key: tuple, build: Callable[[], tuple]):
    """(combination numbers of the documents as a resident plane,
    `ComboSpace`) under `key` (the fields' tuple first): `build`
    (`combo_space`) runs once a segment on the host, the plane then lives
    on the device for the segment's lifetime, in the HBM ledger with the
    bucket planes (`segment_plane`)."""
    return segment_plane(seg, "_combo_plane_cache", key,
                         "agg_bucket_plane", BUCKET_PLANE_STATS, build)


def _multi_terms_space(seg: Segment, ctx: ShardContext,
                       fields: Tuple[str, ...]):
    """`combo_space` of a `multi_terms` source list; documents missing ANY
    source are excluded (-1), matching reference MultiTermsAggregator."""
    per_source, sources = _multi_terms_sources(seg, ctx, fields)
    return combo_space(per_source, sources, (False,) * len(fields),
                       seg.ndocs)


def multi_terms_plane(seg: Segment, ctx: ShardContext,
                      fields: Tuple[str, ...]):
    """(plane, `ComboSpace`) of a `multi_terms` source list."""
    return combo_plane(seg, (tuple(fields), "multi_terms"),
                       lambda: _multi_terms_space(seg, ctx, fields))


def multi_terms_cache(seg: Segment, ctx: ShardContext, node, fields: Tuple[str, ...]):
    """(`ComboSpace` as the vocabulary of key tuples, combined doc-major
    ordinal i32[ndocs_pad] on the HOST) for the mesh path, which restacks
    the segments' ordinals into one index-wide space
    (`parallel/service.py`); the executor's launches read
    `multi_terms_plane`."""
    cache = getattr(seg, "_multi_terms_cache", None)
    if cache is None:
        cache = seg._multi_terms_cache = {}
    if fields not in cache:
        ids, space = _multi_terms_space(seg, ctx, fields)
        ords_out = np.full(next_pow2(seg.ndocs), -1, np.int32)
        ords_out[: seg.ndocs] = ids
        cache[fields] = (space, ords_out)
    return cache[fields]


def col_sum(seg: Segment, field: str) -> Tuple[float, int]:
    """(Σ values, present count) of a numeric column, f64, cached per segment
    (segments are immutable apart from deletes, which don't need to perturb a
    scoring shift)."""
    cache = getattr(seg, "_col_sum_cache", None)
    if cache is None:
        cache = seg._col_sum_cache = {}
    if field not in cache:
        col = seg.numeric_cols.get(field)
        if col is None or not col.present.any():
            cache[field] = (0.0, 0)
        else:
            cache[field] = (float(col.values[col.present].astype(np.float64).sum()),
                            int(col.present.sum()))
    return cache[field]


def kw_doc_counts(seg: Segment, field: str) -> Dict[str, int]:
    """Background per-value doc counts over the segment's live docs
    (significant_terms superset statistics); invalidated by deletes via
    `live_gen`."""
    cache = getattr(seg, "_kw_doc_count_cache", None)
    if cache is None or cache.get("__gen") != seg.live_gen:
        cache = seg._kw_doc_count_cache = {"__gen": seg.live_gen}
    if field in cache:
        return cache[field]
    col = seg.keyword_cols.get(field)
    out: Dict[str, int] = {}
    if col is not None and len(col.vocab):
        live_vals = seg.live[col.doc_of_value]
        counts = np.bincount(col.ords[live_vals], minlength=len(col.vocab))
        out = {col.vocab[i]: int(c) for i, c in enumerate(counts) if c > 0}
    cache[field] = out
    return out
