"""The trip generator and segment wrapper of the deployment kind
`nyc_taxis` (OpenSearch Benchmark `nyc_taxis`: yellow-cab trips of 2015,
18 fields a document).

No data set is in the image and there is no network, so the trips are
synthetic, from the configuration's `corpus_seed` and `generator`
parameters (docs/BENCH_CORPUS.md, "nyc_taxis", has the laws and what they
stand in for). Rows come in the source's file order: month by month, and
in no order of time inside a month. `generate` makes the columns in bulk
with numpy (money and distance as whole hundredths, times as whole epoch
seconds); `plant_index` wraps them as one product `Segment` under an index
the client creates through its own API with the workload's mapping,
holding what the refresh path would have built for those 18 fields: eight
`scaled_float` columns (float64 of hundredths / 100, as
`mappings.coerce_value` stores them), two `date` columns and one `integer`
(int64), two `geo_point` columns, and the five keywords' columns and term
postings. Positions are not built (no traffic here asks a phrase)."""

from __future__ import annotations

import time

import numpy as np

from corpus import _LazyIds
from http_logs_events import _term_postings

YEAR_START_S = 1420070400       # 2015-01-01T00:00:00Z
YEAR_DAYS = 365
DAY_S = 86400
MONTH_DAYS = (31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31)
DATE_FORMAT = "yyyy-MM-dd HH:mm:ss"

MONEY = ("total_amount", "fare_amount", "tip_amount", "tolls_amount",
         "extra", "mta_tax", "improvement_surcharge")
SCALED = MONEY + ("trip_distance",)
DATES = ("pickup_datetime", "dropoff_datetime")
GEO = ("pickup_location", "dropoff_location")
KEYWORDS = ("vendor_id", "payment_type", "rate_code_id",
            "store_and_fwd_flag", "trip_type")

MAPPING = {"properties": dict(
    [(f, {"type": "scaled_float", "scaling_factor": 100}) for f in SCALED]
    + [(f, {"type": "date", "format": DATE_FORMAT}) for f in DATES]
    + [("passenger_count", {"type": "integer"})]
    + [(f, {"type": "geo_point"}) for f in GEO]
    + [(f, {"type": "keyword"}) for f in KEYWORDS])}
assert len(MAPPING["properties"]) == 18

# the city's box the locations stay inside (lat, lon)
BOX = ((40.55, 40.95), (-74.10, -73.70))


def hour_weights() -> np.ndarray:
    """Relative pickup rate of every hour of 2015 (UTC): a daily cycle
    (least at 10:00 UTC, the city's 05:00; most twelve hours later) times a
    weekly one (Friday and Saturday a tenth over, Sunday and Monday a
    tenth under)."""
    hour = np.arange(YEAR_DAYS * 24, dtype=np.int64)
    tod, day = hour % 24, hour // 24
    daily = 1.0 + 0.6 * np.cos(2 * np.pi * (tod - 22.0) / 24.0)
    dow = (day + 3) % 7             # 2015-01-01 was a Thursday: 0 = Monday
    weekly = np.asarray([0.9, 1.0, 1.0, 1.05, 1.1, 1.1, 0.9])[dow]
    return daily * weekly


def _choice(rng, n: int, shares: dict) -> np.ndarray:
    """`n` draws of an index into `shares`' keys, by their shares, int8."""
    cum = np.cumsum([float(p) for p in shares.values()])
    u = rng.random(n, dtype=np.float32)
    u *= np.float32(cum[-1])
    return np.minimum(np.searchsorted(cum.astype(np.float32), u),
                      len(cum) - 1).astype(np.int8)


def _pickups(rng, ndocs: int) -> np.ndarray:
    """Pickup times in whole epoch seconds, in the source's file order:
    the months one after another, a month's trips in no order of time."""
    w = hour_weights()
    month_of_hour = np.repeat(np.arange(12), np.asarray(MONTH_DAYS) * 24)
    per_month = rng.multinomial(
        ndocs, np.bincount(month_of_hour, weights=w) / w.sum())
    out = np.empty(ndocs, np.int64)
    at, hour0 = 0, 0
    for m, n in enumerate(per_month):
        hours = MONTH_DAYS[m] * 24
        cum = np.cumsum(w[hour0: hour0 + hours])
        u = rng.random(n)
        u *= cum[-1]
        part = out[at: at + n]
        part[:] = np.minimum(np.searchsorted(cum, u), hours - 1) + hour0
        part *= 3600
        part += (rng.random(n, dtype=np.float32) * 3600).astype(np.int64)
        at, hour0 = at + n, hour0 + hours
    out += YEAR_START_S
    return out


def _trip(rng, pickup_s: np.ndarray, gen: dict):
    """Distance (hundredths of a mile), duration (s) and drop-off (epoch s)
    of every trip: a lognormal distance capped at the generator's
    `distance_max`, a lognormal speed, a lognormal wait."""
    n = len(pickup_s)
    dist = rng.lognormal(np.log(float(gen["distance_median"])),
                         float(gen["distance_sigma"]), n)
    np.minimum(dist, float(gen["distance_max"]), out=dist)
    dist_c = np.rint(dist * 100).astype(np.int32)
    speed = rng.lognormal(np.log(float(gen["speed_median_mph"])),
                          float(gen["speed_sigma"]), n)
    secs = dist_c / 100.0 / speed * 3600.0
    secs += rng.lognormal(np.log(float(gen["wait_median_s"])), 0.6, n)
    np.minimum(secs, float(gen["duration_max_s"]), out=secs)
    duration = np.maximum(secs.astype(np.int64), 1)
    return dist_c, duration, pickup_s + duration


def _money(rng, pickup_s, dist_c, duration, payment, gen: dict) -> dict:
    """The seven amounts in whole cents: the meter's fare from distance and
    time, the night and rush-hour extras from the pickup's hour, the fixed
    taxes, a toll on one trip in twenty, a tip on card payments only."""
    n = len(dist_c)
    fare = (250 + 50 * np.rint(dist_c / 20.0)
            + 50 * np.rint(0.4 * duration / 60.0)).astype(np.int32)
    tod = (pickup_s % DAY_S) // 3600
    dow = ((pickup_s - YEAR_START_S) // DAY_S + 3) % 7
    extra = np.where((tod >= 1) & (tod < 11), 50,      # 20:00-06:00 local
                     np.where((dow < 5) & (tod >= 21), 100, 0)
                     ).astype(np.int32)
    tolls = np.where(rng.random(n, dtype=np.float32)
                     < np.float32(gen["toll_share"]),
                     int(gen["toll_cents"]), 0).astype(np.int32)
    share = rng.normal(float(gen["tip_share_mean"]),
                       float(gen["tip_share_sigma"]), n)
    np.clip(share, 0.0, 1.0, out=share)
    share[rng.random(n, dtype=np.float32)
          < np.float32(gen["card_no_tip_share"])] = 0.0
    card = payment == list(gen["payment_type_shares"]).index("1")
    tip = np.where(card, np.rint(fare * share), 0).astype(np.int32)
    mta = np.full(n, 50, np.int32)
    surcharge = np.full(n, 30, np.int32)
    return {"fare_amount": fare, "extra": extra, "mta_tax": mta,
            "improvement_surcharge": surcharge, "tolls_amount": tolls,
            "tip_amount": tip,
            "total_amount": fare + extra + mta + surcharge + tolls + tip}


def _locations(rng, n: int):
    """(lat f32, lon f32) around midtown, clipped to the city's box."""
    lat = rng.normal(40.752, 0.035, n).astype(np.float32)
    lon = rng.normal(-73.978, 0.035, n).astype(np.float32)
    np.clip(lat, BOX[0][0], BOX[0][1], out=lat)
    np.clip(lon, BOX[1][0], BOX[1][1], out=lon)
    return lat, lon


def generate(ndocs: int, seed: int, gen: dict) -> dict:
    """The columns of `ndocs` trips in file order: `pickup_s` / `dropoff_s`
    i64 epoch seconds, the eight `scaled_float` fields as `<field>_c` i32
    hundredths, `passenger_count` i8, the four coordinate planes f32, and
    every keyword as `<field>` i8 (an index into `<field>_values`, the
    sorted values the generator's shares name). Groups of columns have
    random streams of their own (spawned from `seed`) and are drawn side by
    side on threads (numpy releases the lock)."""
    from concurrent.futures import ThreadPoolExecutor
    r_time, r_trip, r_money, r_pass, r_loc, r_kw = np.random.default_rng(
        [int(seed), 32]).spawn(6)
    out = {}
    with ThreadPoolExecutor(6) as pool:
        keywords = {f: pool.submit(_choice, r, ndocs, gen[f + "_shares"])
                    for f, r in zip(KEYWORDS, r_kw.spawn(len(KEYWORDS)))}
        passengers = pool.submit(_choice, r_pass, ndocs,
                                 gen["passenger_count_shares"])
        locs = [pool.submit(_locations, r, ndocs) for r in r_loc.spawn(2)]
        pickup_s = _pickups(r_time, ndocs)
        dist_c, duration, dropoff_s = _trip(r_trip, pickup_s, gen)
        for f in KEYWORDS:
            # ordinals follow the sorted values, as a keyword column's do
            names = list(gen[f + "_shares"])
            order = sorted(range(len(names)), key=names.__getitem__)
            rank = np.empty(len(names), np.int8)
            rank[order] = np.arange(len(names), dtype=np.int8)
            out[f] = rank[keywords[f].result()]
            out[f + "_values"] = [names[i] for i in order]
        payment = keywords["payment_type"].result()
        money = _money(r_money, pickup_s, dist_c, duration, payment, gen)
        out.update({f + "_c": v for f, v in money.items()})
        counts = np.asarray([int(k) for k in gen["passenger_count_shares"]],
                            np.int8)
        out["passenger_count"] = counts[passengers.result()]
        for f, fut in zip(GEO, locs):
            out[f + "_lat"], out[f + "_lon"] = fut.result()
    out.update(pickup_s=pickup_s, dropoff_s=dropoff_s, trip_distance_c=dist_c)
    return out


def date_string(epoch_s: int) -> str:
    """`2015-01-01 00:12:34` of a whole epoch second (the mapping's
    `yyyy-MM-dd HH:mm:ss`)."""
    return time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime(epoch_s))


def day_string(epoch_s: int) -> str:
    """`21/01/2015` (the requests' `dd/MM/yyyy`)."""
    return time.strftime("%d/%m/%Y", time.gmtime(epoch_s))


class _LazySources:
    """A trip's `_source`, made on demand from the columns."""

    def __init__(self, trips: dict):
        self.t = trips

    def __len__(self):
        return len(self.t["pickup_s"])

    def __getitem__(self, i):
        t = self.t
        src = {f: int(t[f + "_c"][i]) / 100.0 for f in SCALED}
        src["pickup_datetime"] = date_string(int(t["pickup_s"][i]))
        src["dropoff_datetime"] = date_string(int(t["dropoff_s"][i]))
        src["passenger_count"] = int(t["passenger_count"][i])
        for f in GEO:
            src[f] = [float(t[f + "_lon"][i]), float(t[f + "_lat"][i])]
        for f in KEYWORDS:
            src[f] = t[f + "_values"][int(t[f][i])]
        return src


def plant_index(client, index: str, trips: dict, settings: dict):
    """Create `index` through the client with the workload's mapping and
    plant one segment holding the 18 fields of `trips`. -> the Segment."""
    from concurrent.futures import ThreadPoolExecutor

    from opensearch_tpu.index.segment import (GeoColumn, KeywordColumn,
                                              NumericColumn, Segment)
    client.indices.create(index, {"settings": settings, "mappings": MAPPING})
    svc = client.node.indices[index]
    ndocs = len(trips["pickup_s"])
    present = np.ones(ndocs, bool)
    docs = np.arange(ndocs, dtype=np.int32)
    starts = np.arange(ndocs + 1, dtype=np.int64)

    def keyword(f):
        # only the values that occur are terms, as a refresh finds them
        rows, values = trips[f], trips[f + "_values"]
        seen = np.flatnonzero(np.bincount(rows, minlength=len(values)))
        rank = np.full(len(values), -1, np.int32)
        rank[seen] = np.arange(len(seen), dtype=np.int32)
        ords = rank[rows]
        vocab = [values[i] for i in seen]
        return (_term_postings(f, vocab, ords),
                KeywordColumn(field=f, vocab=vocab, starts=starts, ords=ords,
                              doc_of_value=docs, min_ord=ords))

    def scaled(f):
        # hundredths / 100 in float64: `round(v * 100) / 100` of the source
        return NumericColumn(field=f, kind="float",
                             values=trips[f + "_c"] / 100.0, present=present)

    def whole(f, values):
        return NumericColumn(field=f, kind="int",
                             values=np.asarray(values, np.int64),
                             present=present)

    with ThreadPoolExecutor(8) as pool:
        kw = {f: pool.submit(keyword, f) for f in KEYWORDS}
        numeric = {f: pool.submit(scaled, f) for f in SCALED}
        numeric = {f: fut.result() for f, fut in numeric.items()}
        numeric["pickup_datetime"] = whole("pickup_datetime",
                                           trips["pickup_s"] * 1000)
        numeric["dropoff_datetime"] = whole("dropoff_datetime",
                                            trips["dropoff_s"] * 1000)
        numeric["passenger_count"] = whole("passenger_count",
                                           trips["passenger_count"])
        kw = {f: fut.result() for f, fut in kw.items()}
    seg = Segment(
        name="nyctaxis0", ndocs=ndocs,
        postings={f: pb for f, (pb, _col) in kw.items()},
        numeric_cols=numeric,
        keyword_cols={f: col for f, (_pb, col) in kw.items()},
        geo_cols={f: GeoColumn(field=f, lat=trips[f + "_lat"],
                               lon=trips[f + "_lon"], present=present)
                  for f in GEO},
        doc_lens={}, text_stats={}, ids=[], sources=[])
    seg.ids = _LazyIds(ndocs)
    seg.sources = _LazySources(trips)
    seg.id2doc = {}
    seg.live = np.ones(ndocs, dtype=bool)
    svc.shards[0].segments = [seg]
    svc.generation += 1
    return seg
