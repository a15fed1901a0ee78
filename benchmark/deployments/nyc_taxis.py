"""The deployment kind `nyc_taxis`: OpenSearch Benchmark's `nyc_taxis`
workload (a year of yellow-cab trips in one index) served as its search
operations: a numeric range, a `histogram` with a `stats` sub-aggregation,
an `auto_date_histogram` and a `date_histogram` over a date column in no
row order, and field sorts, all through the column executor
(`compiler.run_segment`'s `executor_program`), none through the BM25
kernels.

What a reader of `README.md` needs, by member:

- `build`: first the program's counters this kind's metrics read are
  resolved (`compiler.EXECUTOR_STATS` with `launches`, `AGG_STATS`,
  `BUCKET_PLANE_STATS`, `RANK_PLANE_STATS`); a program without them exits
  at once, naming them, before any data is made (such a program also takes
  an `auto_date_histogram`'s interval from the column's span, adds a
  bucket's sum in one float32 and does not read `dd/MM/yyyy`: it would not
  hold the rule). Then `nyc_taxis_trips.generate` makes the
  configuration's `ndocs` trips from its `corpus_seed` and `generator` (the
  collection is the deployment's fixed data set, like the other
  configurations'; `--seed` orders the pool, samples the check and draws
  its fresh requests), `plant_index` wraps them as one segment with all 18
  fields under an index created with the workload's mapping, and the
  segment's device arrays are promoted and waited for. The read-out
  carries the rows, the postings and the device arrays' bytes by field.
- `stream`: a traffic file's `generator` is a key of `GENERATORS`;
  `analyst_rotation` deals the eight operations in a fixed rotation
  (`SHAPES` of `nyc_taxis_reference.py`), each OSB's body with its bounds
  drawn from the traffic file's `params` (`_draw`). A twin moves the upper
  bound by one hundredth or one day: the same compiled shapes, another
  body. `weight` is the bound's width in its own unit.
- `hold`: `nyc_taxis_reference.Reference` over the run's own columns and
  its rule (`nyc_taxis_control.py` is the control); its read-out adds the
  HBM ledger's bytes by tenant as they stand then.
- `counters`: the four counter groups, flat (`executor.launches`,
  `aggs.scatter.updates` ...)."""

from __future__ import annotations

import time

import numpy as np

import nyc_taxis_reference as reference
import nyc_taxis_trips as trips

COUNTER_GROUPS = {"executor": ("EXECUTOR_STATS", ("launches",)),
                  "aggs": ("AGG_STATS", ("scatter.updates", "blocked.rows",
                                         "bucketed_sub.launches",
                                         "bucketed_sub.buckets",
                                         "auto_date.requests",
                                         "auto_date.refine_launches")),
                  "aggs.bucket_plane": ("BUCKET_PLANE_STATS", ()),
                  "sort.rank_plane": ("RANK_PLANE_STATS", ())}
DAY_S = trips.DAY_S


def _counter_groups() -> dict:
    """prefix -> the program's counter group; exits where a group or one
    of the counters this kind reads is missing."""
    from opensearch_tpu.search import compiler
    groups, lacks = {}, []
    for prefix, (name, keys) in COUNTER_GROUPS.items():
        group = getattr(compiler, name, None)
        if group is None:
            lacks.append(f"compiler.{name} ({prefix}.*)")
            continue
        lacks += [f"{prefix}.{k}" for k in keys if k not in group]
        groups[prefix] = group
    if lacks:
        raise SystemExit(
            "benchmark: deployment kind 'nyc_taxis' needs a program with "
            f"the counters {', '.join(lacks)}; this one has none (it takes "
            "an auto_date_histogram's interval from the column's span, "
            "adds a bucket's sum in one float32 accumulator and reads no "
            "dd/MM/yyyy)")
    return groups


def build(config: dict, seed: int, client, index: str) -> dict:
    import jax

    _counter_groups()
    t0 = time.time()
    columns = trips.generate(int(config["ndocs"]),
                             int(config["corpus_seed"]), config["generator"])
    generate_s = time.time() - t0
    if config.get("cluster_settings"):      # the deployment's own limits
        client.cluster.put_settings(config["cluster_settings"])
    seg = trips.plant_index(client, index, columns, config["index_settings"])
    build_s = time.time() - t0

    t0 = time.time()
    jax.block_until_ready(seg.device_arrays())
    promote_s = time.time() - t0
    return {"columns": columns, "build_s": build_s, "promote_s": promote_s,
            "readout": {
                "rows": seg.ndocs, "rows_padded": seg.ndocs_pad,
                "generate_s": generate_s,
                "postings": {f: pb.size for f, pb in seg.postings.items()},
                "device_bytes": _device_bytes(seg.device_arrays())}}


def _cents(c: int) -> float:
    return c / 100.0


def _spec(shape: str, lo: int, hi: int, page: int) -> dict:
    """The operation `shape` over [lo, hi): hundredths for the two numeric
    ranges, whole days since 2015-01-01 for the date ranges (`hi` is the
    day after the last one the body names)."""
    out = {"shape": shape, "page": page, "weight": float(hi - lo),
           "draw": (lo, hi)}
    day0 = trips.YEAR_START_S
    if shape == "range":
        out.update(on="total_amount_c", lo=lo, hi=hi, body={"query": {
            "range": {"total_amount": {"gte": _cents(lo),
                                       "lt": _cents(hi)}}}})
    elif shape == "distance_amount_agg":
        out.update(on="trip_distance_c", lo=lo, hi=hi, body={
            "size": 0,
            "query": {"bool": {"filter": {"range": {"trip_distance": {
                "gte": _cents(lo), "lt": _cents(hi)}}}}},
            "aggs": {reference.AGG_NAME[shape]: {
                "histogram": {"field": "trip_distance", "interval": 1},
                "aggs": {reference.STATS_NAME: {
                    "stats": {"field": "total_amount"}}}}}})
    elif shape in ("autohisto_agg", "date_histogram_agg"):
        agg = ({"auto_date_histogram": {"field": "dropoff_datetime",
                                        "buckets": reference.AUTO_BUCKETS}}
               if shape == "autohisto_agg" else
               {"date_histogram": {"field": "dropoff_datetime",
                                   "calendar_interval": "day"}})
        out.update(on="dropoff_ms", lo=(day0 + lo * DAY_S) * 1000,
                   hi=(day0 + hi * DAY_S) * 1000, body={
            "size": 0,
            "query": {"range": {"dropoff_datetime": {
                "gte": trips.day_string(day0 + lo * DAY_S),
                "lte": trips.day_string(day0 + (hi - 1) * DAY_S),
                "format": "dd/MM/yyyy"}}},
            "aggs": {reference.AGG_NAME[shape]: agg}})
    else:
        order, _sort, field = shape.split("_", 2)
        out.update(on="pickup_ms", lo=(day0 + lo * DAY_S) * 1000,
                   hi=(day0 + hi * DAY_S) * 1000, body={
            "query": {"range": {"pickup_datetime": {
                "gte": trips.date_string(day0 + lo * DAY_S),
                "lte": trips.date_string(day0 + hi * DAY_S - 1)}}},
            "size": page, "sort": [{field: order}]})
    return out


def _draw(rng, shape: str, p: dict) -> tuple:
    """(lo, hi) of one body, in the units of `_spec`."""
    def whole(lo_hi):
        return int(rng.integers(int(lo_hi[0]), int(lo_hi[1]) + 1))
    if shape == "range":
        lo = whole(p["range_start_cents"])
        return lo, lo + whole(p["range_width_cents"])
    if shape == "distance_amount_agg":
        return 0, whole(p["distance_upper_hundredths"])
    if shape in ("autohisto_agg", "date_histogram_agg"):
        days = whole(p[shape + "_days"])
    else:
        lo_d, hi_d = p["sort_days"]
        days = int(np.exp(rng.uniform(np.log(lo_d), np.log(hi_d + 1))))
    # one day short of the year, so that a twin's day more stays inside it
    start = int(rng.integers(0, trips.YEAR_DAYS - days))
    return start, start + days


class _Stream:
    def __init__(self, built: dict, traffic: dict, seed: int):
        self.params = traffic["params"]
        self.page = int(traffic["size"])
        self._seen, self._turn = set(), 0
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self._rng = np.random.default_rng([seed, 2])

    def take(self, n: int) -> list:
        out = []
        while len(out) < n:
            shape = reference.SHAPES[self._turn % len(reference.SHAPES)]
            lo, hi = _draw(self._rng, shape, self.params)
            # a twin is the draw with `hi` one on: neither may come twice
            if {(shape, lo, hi), (shape, lo, hi + 1)} & self._seen:
                continue
            self._seen |= {(shape, lo, hi), (shape, lo, hi + 1)}
            self._turn += 1
            out.append(_spec(shape, lo, hi, self.page))
        return out

    def twin(self, spec: dict) -> dict:
        """The upper bound one hundredth or one day on."""
        lo, hi = spec["draw"]
        return _spec(spec["shape"], lo, hi + 1, spec["page"])


# the request generators a traffic file of this kind may name
GENERATORS = {"analyst_rotation": _Stream}


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    name = traffic["generator"]
    if name not in GENERATORS:
        raise SystemExit(f"benchmark: deployment kind 'nyc_taxis' has "
                         f"no request generator {name!r} "
                         f"(has {sorted(GENERATORS)})")
    return GENERATORS[name](built, traffic, seed)


def _device_bytes(tree: dict) -> dict:
    """Bytes of a segment's device arrays by group and field."""
    from opensearch_tpu.index.segment import _tree_nbytes
    out = {}
    for group, held in tree.items():
        if isinstance(held, dict):
            out.update({f"{group}.{f}": _tree_nbytes(a)
                        for f, a in held.items()})
        else:
            out[group] = int(held.nbytes)
    return out


def reference_of(built: dict) -> reference.Reference:
    """The run's reference, made once (its sorted orders are built on the
    first sorted page and kept)."""
    if "reference" not in built:
        built["reference"] = reference.Reference(built["columns"])
    return built["reference"]


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    """(spec, response) pairs held to the reference by its rule; the
    read-out also says what the device holds now, after warm-up and
    window: the ledger's bytes by tenant and the planes' counters."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    out = reference.hold(held, reference_of(built))
    out["residency"] = {
        "hbm_ledger_bytes": {k: t["bytes"] for k, t in
                             LEDGER.snapshot()["tenants"].items()},
        "planes": {k: v for k, v in counters(None).items()
                   if "plane" in k}}
    return out


def counters(client) -> dict:
    return {f"{prefix}.{k}": v for prefix, group in _counter_groups().items()
            for k, v in group.items()}
