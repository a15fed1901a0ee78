"""Query insights: workload fingerprinting + heavy-hitter attribution.

The observatory stack (telemetry PR 3, flight recorder PR 6, cost
accounting PR 7, fleet SLOs PR 10) can say *that* a lane's latency SLO
is burning, *which* node is slow and *how many* bytes a query moved —
but nothing could say *which queries* are responsible. This module
closes that gap, the reference analog of the query-insights plugin
(top-N queries by latency/cost, grouped by query shape): every search is
fingerprinted into a bounded query *shape*, per-shape rolling aggregates
ride a fixed-capacity heavy-hitter sketch, and the result federates
cluster-wide and feeds SLO-burn forensics — the attribution input the
ROADMAP item-1 load-shed actuator needs ("shed batch-lane load" is only
actionable when the engine can name the load).

Design constraints:

- **Fingerprints carry structure, never text.** A shape is the
  normalized DSL skeleton (query-node kinds + field names, values
  stripped) plus coarse features (term count, agg kinds, sort kind,
  size bucket, lane). Raw query/body strings never land in a
  fingerprint feature, a metric label, or a wire payload — oslint
  OSL602 enforces the label half statically.
- **Memory is O(capacity), not O(workload cardinality).** Per-shape
  aggregates live in a space-saving (Misra-Gries-family) sketch: at
  most `capacity` monitored shapes, eviction by minimum estimated
  count. The classic guarantees hold (N records, capacity c):
  every monitored shape reports `true <= est <= true + error` with
  `error <= N/c`, and any shape with true frequency > N/c is
  monitored. A 10k-distinct-shape workload costs the same bytes as a
  10-shape one. The recent-activity window is a `deque(maxlen=...)`
  ring (OSL602's bounded-growth discipline).
- **Merge is commutative.** Federation (`GET /_insights/top_queries`
  on a cluster) merges per-node sketch wires: counts and errors sum
  over the key union, latency sketches merge bin-wise through the
  DDSketch algebra `utils/metrics.py` proved for `_cluster/stats`,
  and a key absent from a *full* wire adds that wire's minimum count
  to the merged error (absence from a non-full sketch means a true
  zero). Union + sum is order-free; the final truncation to capacity
  uses the deterministic (count desc, key asc) order — so any member
  can coordinate and every coordinator answers identically.
- **The hot path is one lock + O(1) dict ops.** Recording at the
  `Node.search` boundary takes the sketch lock for a dict upsert;
  eviction's O(capacity) min-scan only runs when a NEW shape arrives
  at a full sketch. Disabled (`enabled = False`) the
  per-search cost is one attribute read (the flight-recorder
  discipline; tests pin the guard).

Attribution loop (docs/OBSERVABILITY.md "Query insights"):

- an `slo.burn` alert carries the top-K fingerprints active in the
  offending window (obs/slo.py enriches its dump bundle),
- each top-query entry links its WORST flight-recorder timeline id,
- slowlog entries carry the request's fingerprint,
- `/_metrics` exports only the top-K (labels are the shape hash).
"""

from __future__ import annotations

import contextvars
import hashlib
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

from ..utils.metrics import sketch_percentile

__all__ = ["fingerprint", "SpaceSavingSketch", "merge_wires",
           "QueryInsights", "INSIGHTS", "begin", "finish", "current",
           "note_bytes", "note_blocks", "note_escalation",
           "note_cache_hit", "note_rejection_source"]

TOP_BY = ("latency", "count", "bytes")

# shape-walk guards: a hostile/degenerate body must cost bounded work
_MAX_DEPTH = 12
_MAX_CHILDREN = 24
_MAX_SHAPE_LEN = 512

# query kinds whose spec is {field: value-ish}: the field name is
# structure, the value is stripped; match-ish kinds contribute a term
# count (whitespace tokens of the value — a count, never the text)
_FIELD_KINDS = frozenset((
    "match", "match_phrase", "match_phrase_prefix", "match_bool_prefix",
    "term", "terms", "prefix", "wildcard", "regexp", "fuzzy", "range",
    "rank_feature", "distance_feature", "geo_distance", "geo_shape",
    "geo_bounding_box", "intervals", "span_term", "knn",
    "neural_sparse"))
_TERMY_KINDS = frozenset((
    "match", "match_phrase", "match_phrase_prefix", "match_bool_prefix"))
_COMPOUND_LIST_KEYS = ("must", "should", "must_not", "filter")


def _term_count(v) -> int:
    if isinstance(v, str):
        return len(v.split())
    if isinstance(v, dict):
        q = v.get("query")
        if isinstance(q, str):
            return len(q.split())
        return 1
    if isinstance(v, (list, tuple)):
        return len(v)
    return 1


class _ShapeStats:
    __slots__ = ("terms", "depth", "clauses")

    def __init__(self):
        self.terms = 0
        self.depth = 0
        self.clauses = 0


def _shape_node(node, depth: int, st: _ShapeStats) -> str:
    """Normalized skeleton of one query node: kind names and field
    names survive, every value is stripped. Bounded depth/fan-out."""
    if depth > _MAX_DEPTH or not isinstance(node, dict) or not node:
        return "?"
    st.depth = max(st.depth, depth)
    kind = sorted(node)[0] if len(node) > 1 else next(iter(node))
    spec = node.get(kind)
    st.clauses += 1
    if kind == "bool" and isinstance(spec, dict):
        parts = []
        for ck in _COMPOUND_LIST_KEYS:
            sub = spec.get(ck)
            if sub is None:
                continue
            subs = sub if isinstance(sub, list) else [sub]
            inner = ",".join(_shape_node(s, depth + 1, st)
                             for s in subs[:_MAX_CHILDREN])
            parts.append(f"{ck}:[{inner}]")
        return f"bool({','.join(parts)})"
    if kind in ("dis_max", "hybrid") and isinstance(spec, dict):
        subs = spec.get("queries") or []
        inner = ",".join(_shape_node(s, depth + 1, st)
                         for s in subs[:_MAX_CHILDREN])
        return f"{kind}([{inner}])"
    if kind in ("nested", "constant_score", "function_score",
                "script_score", "boosting") and isinstance(spec, dict):
        sub = (spec.get("query") or spec.get("positive"))
        inner = _shape_node(sub, depth + 1, st) if sub else ""
        return f"{kind}({inner})"
    if kind in ("multi_match", "combined_fields", "query_string",
                "simple_query_string") and isinstance(spec, dict):
        fields = spec.get("fields")
        nf = len(fields) if isinstance(fields, list) else 1
        st.terms += _term_count(spec)
        return f"{kind}(fields:{nf})"
    if kind in _FIELD_KINDS and isinstance(spec, dict) and spec:
        field = sorted(spec)[0]
        if kind in _TERMY_KINDS:
            st.terms += _term_count(spec[field])
        elif kind == "terms" and isinstance(spec.get(field),
                                            (list, tuple)):
            st.terms += len(spec[field])
        else:
            st.terms += 1
        return f"{kind}({field})"
    return kind


def _agg_kinds(aggs, depth: int = 0) -> List[str]:
    out: List[str] = []
    if not isinstance(aggs, dict) or depth > 4:
        return out
    for spec in aggs.values():
        if not isinstance(spec, dict):
            continue
        kinds = [k for k in spec if k not in ("aggs", "aggregations")]
        out.extend(sorted(kinds)[:2])
        sub = spec.get("aggs", spec.get("aggregations"))
        if sub:
            out.extend(_agg_kinds(sub, depth + 1))
    return out[:8]


def _sort_kind(body: dict) -> str:
    sort = body.get("sort")
    if not sort:
        return "score"
    fields = []
    for s in (sort if isinstance(sort, list) else [sort]):
        f = s if isinstance(s, str) else (next(iter(s))
                                          if isinstance(s, dict) and s
                                          else "?")
        fields.append("score" if f == "_score" else "field")
    return "+".join(fields[:3]) or "score"


def _size_bucket(body: dict) -> int:
    try:
        size = int(body.get("size", 10))
    except (TypeError, ValueError):
        return 10
    b = 1
    while b < max(size, 1) and b < 65536:
        b <<= 1
    return b


def fingerprint(body: dict, lane: str = "interactive"
                ) -> Tuple[str, str, dict]:
    """-> (key, shape, features): the bounded identity of one search
    body. `key` is a 12-hex digest (the only thing metric labels ever
    carry), `shape` the normalized value-free DSL skeleton, `features`
    the coarse workload descriptors. Never raises — an unparseable
    body fingerprints as the "unparseable" shape."""
    try:
        st = _ShapeStats()
        q = body.get("query") if isinstance(body, dict) else None
        shape = (_shape_node(q, 1, st) if isinstance(q, dict)
                 else "match_all")[:_MAX_SHAPE_LEN]
        aggs = _agg_kinds(body.get("aggs", body.get("aggregations")))
        sort = _sort_kind(body)
        size_b = _size_bucket(body)
        knn = bool(body.get("knn"))
        # term COUNT rides the identity as a pow2 bucket: a 1-term and
        # a 30-term match are different workloads (BM25S: eager-scoring
        # wins are term-count-dependent) but the bucket keeps identity
        # cardinality bounded. depth/clauses are fully determined by
        # the shape string and need no separate canon slot.
        terms_b = 1
        while terms_b < max(st.terms, 1) and terms_b < 256:
            terms_b <<= 1
        # vector/hybrid workload descriptors (ISSUE 15): a hybrid body
        # carries its sub-query COUNT and the set of retrieval-family
        # kinds as identity — a 2-sub lexical+knn hybrid and a 3-sub
        # hybrid with learned-sparse are different workloads the
        # heavy-hitter attribution (and the PR-14 remediator's shed
        # match) must tell apart. knn also derives from the QUERY tree
        # (query.knn / a knn sub-query), not just the ES-style body key.
        sub_kinds: List[str] = []
        hybrid_n = 0
        if isinstance(q, dict) and isinstance(q.get("hybrid"), dict):
            subs = q["hybrid"].get("queries")
            if isinstance(subs, list):
                hybrid_n = len(subs)
                sub_kinds = sorted({next(iter(s)) for s in
                                    subs[:_MAX_CHILDREN]
                                    if isinstance(s, dict) and s})[:8]
        # the FEATURE flag derives from every vector form (ES-style
        # body key, query.knn, knn sub-queries) — but the CANON slot
        # keeps only the body-key bit it always carried: query.knn and
        # hybrid sub-kinds are already identity-bearing via the shape
        # string / the hybrid suffix below, and re-deriving the canon
        # flag would change every pre-existing query.knn digest
        knn_feature = knn or "knn(" in shape or "knn" in sub_kinds
        features = {"kind": shape.split("(", 1)[0], "terms": st.terms,
                    "terms_bucket": terms_b, "depth": st.depth,
                    "clauses": st.clauses, "aggs": aggs, "sort": sort,
                    "size_bucket": size_b, "lane": lane,
                    "knn": knn_feature,
                    "hybrid": hybrid_n > 0, "sub_queries": hybrid_n,
                    "sub_kinds": sub_kinds}
        canon = (f"{shape}|lane={lane}|sort={sort}|"
                 f"aggs={','.join(aggs)}|size={size_b}|knn={int(knn)}|"
                 f"terms={terms_b}")
        if hybrid_n:
            # appended ONLY for hybrid bodies so every pre-existing
            # shape digest stays stable across the format rev
            canon += f"|hybrid={hybrid_n}|subs={','.join(sub_kinds)}"
    except Exception:       # noqa: BLE001 — fingerprinting must never
        # fail a search; a pathological body lands in one bucket
        shape, features = "unparseable", {"kind": "unparseable",
                                          "lane": lane}
        canon = f"unparseable|lane={lane}"
    key = hashlib.sha1(canon.encode("utf-8", "replace")).hexdigest()[:12]
    return key, shape, features


# ---------------------------------------------------------------------
# the space-saving heavy-hitter sketch
# ---------------------------------------------------------------------

class _Entry:
    __slots__ = ("key", "shape", "features", "count", "error",
                 "lat_bins", "lat_count", "lat_sum_ms", "bytes_moved",
                 "blocks_total", "blocks_skipped", "escalations",
                 "cache_hits", "rejections", "errors", "worst_ms",
                 "worst_timeline", "first_seen_mono", "last_seen_mono")

    def __init__(self, key: str, shape: str, features: dict,
                 count: int, error: int, now: float):
        self.key = key
        self.shape = shape
        self.features = features
        self.count = count
        self.error = error
        self.lat_bins: Dict[int, int] = {}
        self.lat_count = 0
        self.lat_sum_ms = 0.0
        self.bytes_moved = 0
        self.blocks_total = 0
        self.blocks_skipped = 0
        self.escalations = 0
        self.cache_hits = 0
        self.rejections = 0
        self.errors = 0
        self.worst_ms = 0.0
        self.worst_timeline = 0
        self.first_seen_mono = now
        self.last_seen_mono = now


def _lat_snapshot(bins: Dict[int, int], count: int,
                  sum_ms: float) -> dict:
    out = {"count": count, "sum_ms": round(sum_ms, 3)}
    for p in (50, 95, 99):
        v = sketch_percentile(bins, count, p)
        out[f"p{p}_ms"] = round(v, 4) if v is not None else None
    return out


class SpaceSavingSketch:
    """Fixed-capacity heavy-hitter summary with per-key rolling
    aggregates. Counts carry the space-saving bounds; the aggregates
    (latency sketch, bytes, skip/escalation/cache/rejection tallies)
    are per-tenure — an evicted-and-readopted shape restarts them,
    which is the honest bounded-memory trade and is documented on the
    wire (`error` prices the count uncertainty)."""

    def __init__(self, capacity: int):
        if capacity < 2:
            raise ValueError("sketch capacity must be >= 2")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}
        self.total_records = 0
        self.evictions = 0

    def record(self, key: str, shape: str, features: dict,
               latency_ms: Optional[float] = None,
               bytes_moved: int = 0, blocks_total: int = 0,
               blocks_skipped: int = 0, escalations: int = 0,
               cache_hit: bool = False, rejected: bool = False,
               error: bool = False, timeline_id: int = 0) -> None:
        now = time.monotonic()
        lat_bin = None
        if latency_ms is not None:
            from ..ops.aggs import ddsketch_bin
            lat_bin = ddsketch_bin(float(latency_ms))
        with self._lock:
            self.total_records += 1
            e = self._entries.get(key)
            if e is None:
                if len(self._entries) >= self.capacity:
                    victim = min(self._entries.values(),
                                 key=lambda v: (v.count, v.key))
                    self._entries.pop(victim.key)
                    self.evictions += 1
                    e = _Entry(key, shape, features,
                               victim.count + 1, victim.count, now)
                else:
                    e = _Entry(key, shape, features, 1, 0, now)
                self._entries[key] = e
            else:
                e.count += 1
            e.last_seen_mono = now
            if lat_bin is not None:
                e.lat_bins[lat_bin] = e.lat_bins.get(lat_bin, 0) + 1
                e.lat_count += 1
                e.lat_sum_ms += float(latency_ms)
                if float(latency_ms) >= e.worst_ms:
                    e.worst_ms = float(latency_ms)
                    if timeline_id:
                        e.worst_timeline = int(timeline_id)
            e.bytes_moved += int(bytes_moved)
            e.blocks_total += int(blocks_total)
            e.blocks_skipped += int(blocks_skipped)
            e.escalations += int(escalations)
            if cache_hit:
                e.cache_hits += 1
            if rejected:
                e.rejections += 1
            if error:
                e.errors += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def min_count(self) -> int:
        with self._lock:
            if not self._entries:
                return 0
            return min(e.count for e in self._entries.values())

    def meta_for(self, keys) -> Dict[str, tuple]:
        """key -> (shape, features, worst_timeline) for the monitored
        subset of `keys` — the windowed read path's metadata join,
        O(|keys|) under the lock instead of a full wire serialization."""
        with self._lock:
            out = {}
            for k in keys:
                e = self._entries.get(k)
                if e is not None:
                    out[k] = (e.shape, dict(e.features),
                              e.worst_timeline)
            return out

    @property
    def full(self) -> bool:
        with self._lock:
            return len(self._entries) >= self.capacity

    def _serialize(self, e: _Entry) -> dict:
        return {"fingerprint": e.key, "shape": e.shape,
                "features": dict(e.features),
                "count": e.count, "error": e.error,
                "latency": {"bins": {str(b): c
                                     for b, c in sorted(e.lat_bins.items())},
                            "count": e.lat_count,
                            "sum_ms": round(e.lat_sum_ms, 3)},
                "bytes_moved": e.bytes_moved,
                "blocks_total": e.blocks_total,
                "blocks_skipped": e.blocks_skipped,
                "escalations": e.escalations,
                "cache_hits": e.cache_hits,
                "rejections": e.rejections,
                "errors": e.errors,
                "worst_ms": round(e.worst_ms, 3),
                "worst_timeline": e.worst_timeline}

    def to_wire(self) -> dict:
        """JSON-safe federation payload (the `/_internal/insights`
        answer). `full` + `min_count` let the merge price absence
        correctly: a key absent from a full sketch may have true count
        up to that sketch's minimum."""
        with self._lock:
            entries = [self._serialize(e)
                       for e in self._entries.values()]
            full = len(self._entries) >= self.capacity
            mn = (min(e.count for e in self._entries.values())
                  if self._entries else 0)
            total = self.total_records
        entries.sort(key=lambda d: (-d["count"], d["fingerprint"]))
        return {"capacity": self.capacity, "total_records": total,
                "full": full, "min_count": mn, "entries": entries}

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self.total_records = 0
            self.evictions = 0


def _derived(d: dict) -> dict:
    """Attach read-side derivations to a serialized entry: latency
    percentiles from the bins, mean bytes/query, block-skip rate."""
    out = dict(d)
    lat = d.get("latency") or {}
    bins = {int(b): int(c) for b, c in (lat.get("bins") or {}).items()}
    out["latency"] = _lat_snapshot(bins, int(lat.get("count", 0)),
                                   float(lat.get("sum_ms", 0.0)))
    cnt = max(int(d.get("count", 0)), 1)
    out["mean_bytes_per_query"] = round(d.get("bytes_moved", 0) / cnt, 1)
    bt = int(d.get("blocks_total", 0))
    out["block_skip_rate"] = (round(d.get("blocks_skipped", 0) / bt, 4)
                              if bt else None)
    return out


def merge_wires(wires: Sequence[dict], capacity: int) -> dict:
    """Commutative merge of sketch wires: counts/errors/aggregates sum
    over the key union, latency bins add bin-wise (the DDSketch merge
    algebra), and a key absent from a FULL wire adds that wire's
    `min_count` to the merged error (its true count there is unknown
    but bounded by the minimum; absence from a non-full sketch is a
    true zero). The result truncates to `capacity` by the
    deterministic (count desc, key asc) order, so coordinator choice
    and scrape arrival order can never change the answer."""
    merged: Dict[str, dict] = {}
    metas = []
    for w in wires:
        if not isinstance(w, dict):
            continue
        metas.append((bool(w.get("full")), int(w.get("min_count", 0)),
                      {e["fingerprint"] for e in w.get("entries", [])}))
        for e in w.get("entries", []):
            k = e["fingerprint"]
            m = merged.get(k)
            if m is None:
                m = {"fingerprint": k, "shape": e.get("shape", ""),
                     "features": dict(e.get("features") or {}),
                     "count": 0, "error": 0,
                     "latency": {"bins": {}, "count": 0, "sum_ms": 0.0},
                     "bytes_moved": 0, "blocks_total": 0,
                     "blocks_skipped": 0, "escalations": 0,
                     "cache_hits": 0, "rejections": 0, "errors": 0,
                     "worst_ms": 0.0, "worst_timeline": 0}
                merged[k] = m
            m["count"] += int(e.get("count", 0))
            m["error"] += int(e.get("error", 0))
            lat, elat = m["latency"], e.get("latency") or {}
            for b, c in (elat.get("bins") or {}).items():
                lat["bins"][b] = lat["bins"].get(b, 0) + int(c)
            lat["count"] += int(elat.get("count", 0))
            lat["sum_ms"] = round(lat["sum_ms"]
                                  + float(elat.get("sum_ms", 0.0)), 3)
            for f in ("bytes_moved", "blocks_total", "blocks_skipped",
                      "escalations", "cache_hits", "rejections",
                      "errors"):
                m[f] += int(e.get(f, 0))
            # tuple compare keeps the merge commutative even when two
            # wires tie on worst_ms (the timeline id breaks the tie
            # deterministically)
            cand = (float(e.get("worst_ms", 0.0)),
                    int(e.get("worst_timeline") or 0))
            if cand > (m["worst_ms"], m["worst_timeline"]):
                m["worst_ms"], m["worst_timeline"] = cand
    # absence pricing: a full wire that does not monitor k may hold up
    # to its min_count occurrences of k — widen the error bound
    for k, m in merged.items():
        for full, mn, keys in metas:
            if full and k not in keys:
                m["error"] += mn
    out = sorted(merged.values(),
                 key=lambda d: (-d["count"], d["fingerprint"]))
    total = sum(int(w.get("total_records", 0)) for w in wires
                if isinstance(w, dict))
    return {"capacity": int(capacity), "total_records": total,
            "full": len(out) > capacity,
            "min_count": (out[-1]["count"] if out else 0),
            "entries": out[: int(capacity)]}


def merge_windowed_wires(wires: Sequence[dict], capacity: int,
                         window_s: float) -> dict:
    """Commutative merge of WINDOWED wires (exact ring aggregates):
    counts, latency sums and bytes add per key; shape metadata comes
    from whichever member still monitors the key. Same deterministic
    truncation order as `merge_wires`."""
    merged: Dict[str, dict] = {}
    for w in wires:
        if not isinstance(w, dict):
            continue
        for e in w.get("entries", []):
            k = e["fingerprint"]
            m = merged.get(k)
            if m is None:
                m = {"fingerprint": k, "count": 0,
                     "latency_sum_ms": 0.0, "max_ms": 0.0,
                     "bytes_moved": 0, "shape": e.get("shape", ""),
                     "worst_timeline": 0}
                merged[k] = m
            m["count"] += int(e.get("count", 0))
            m["latency_sum_ms"] = round(
                m["latency_sum_ms"] + float(e.get("latency_sum_ms",
                                                  0.0)), 3)
            # the worst-timeline link must follow the worst LATENCY
            # (tuple compare: commutative even on max_ms ties), or a
            # federated windowed entry could link a fast node's journal
            cand = (float(e.get("max_ms", 0.0)),
                    int(e.get("worst_timeline") or 0))
            if cand > (m["max_ms"], int(m["worst_timeline"] or 0)):
                m["max_ms"], m["worst_timeline"] = cand
            m["bytes_moved"] += int(e.get("bytes_moved", 0))
            if m["shape"] in ("", "(evicted)") and e.get("shape"):
                m["shape"] = e["shape"]
    out = sorted(merged.values(),
                 key=lambda d: (-d["count"], d["fingerprint"]))
    for m in out:
        m["latency_mean_ms"] = round(
            m["latency_sum_ms"] / max(m["count"], 1), 3)
    return {"capacity": int(capacity), "windowed": True,
            "window_s": float(window_s),
            "total_records": sum(m["count"] for m in out),
            "full": False, "min_count": 0,
            "entries": out[: int(capacity)]}


# ---------------------------------------------------------------------
# the per-request observation (contextvar, the query_cost pattern)
# ---------------------------------------------------------------------

class Observation:
    """One search's in-flight attribution state. Taps along the path
    (cache hit, bytes moved, block skips, escalations, rejection
    source) annotate it; the search boundary records it once."""

    __slots__ = ("key", "shape", "features", "lane", "cache_hit",
                 "bytes_moved", "blocks_total", "blocks_skipped",
                 "escalations", "rejected_by")

    def __init__(self, key: str, shape: str, features: dict, lane: str):
        self.key = key
        self.shape = shape
        self.features = features
        self.lane = lane
        self.cache_hit = False
        self.bytes_moved = 0
        self.blocks_total = 0
        self.blocks_skipped = 0
        self.escalations = 0
        self.rejected_by: Optional[str] = None


_current: contextvars.ContextVar = contextvars.ContextVar(
    "opensearch_tpu_insights_obs", default=None)


def current() -> Optional[Observation]:
    return _current.get()


def begin(body: dict, lane: str = "interactive") -> tuple:
    """Install a fresh observation; returns (obs, token) for the
    paired `finish`. A no-op pair (None, None) when disabled."""
    if not INSIGHTS.enabled:
        return None, None
    key, shape, features = fingerprint(body, lane)
    obs = Observation(key, shape, features, lane)
    return obs, _current.set(obs)


def finish(token, obs: Optional[Observation],
           latency_ms: Optional[float] = None,
           rejected: bool = False, error: bool = False,
           timeline_id: int = 0) -> None:
    """Uninstall and record the observation into the engine."""
    if token is not None:
        _current.reset(token)
    if obs is None or not INSIGHTS.enabled:
        return
    INSIGHTS.record_observation(obs, latency_ms=latency_ms,
                                rejected=rejected or
                                obs.rejected_by is not None,
                                error=error, timeline_id=timeline_id)


def note_bytes(n: int) -> None:
    obs = _current.get()
    if obs is not None:
        obs.bytes_moved += int(n)


def note_blocks(total: int, skipped: int) -> None:
    obs = _current.get()
    if obs is not None:
        obs.blocks_total += int(total)
        obs.blocks_skipped += int(skipped)


def note_escalation() -> None:
    obs = _current.get()
    if obs is not None:
        obs.escalations += 1


def note_cache_hit() -> None:
    obs = _current.get()
    if obs is not None:
        obs.cache_hit = True


def note_rejection_source(source: str) -> None:
    obs = _current.get()
    if obs is not None:
        obs.rejected_by = source


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------

class QueryInsights:
    """Process-singleton insights engine: the sketch, the bounded
    recent-activity ring (windowed queries), and the read surfaces."""

    def __init__(self, capacity: int = 256, window_capacity: int = 4096,
                 enabled: bool = True):
        self.capacity = int(capacity)
        self.window_capacity = int(window_capacity)
        self.enabled = bool(enabled)
        self.sketch = SpaceSavingSketch(self.capacity)
        # recent activity: (t_mono, key, latency_ms, bytes) — bounded
        # ring; deque.append is atomic, reads snapshot via list()
        self._recent: deque = deque(maxlen=self.window_capacity)

    # -- write side --

    def record_observation(self, obs: Observation,
                           latency_ms: Optional[float] = None,
                           rejected: bool = False, error: bool = False,
                           timeline_id: int = 0) -> None:
        if not self.enabled:
            return
        self.sketch.record(
            obs.key, obs.shape, obs.features, latency_ms=latency_ms,
            bytes_moved=obs.bytes_moved, blocks_total=obs.blocks_total,
            blocks_skipped=obs.blocks_skipped,
            escalations=obs.escalations, cache_hit=obs.cache_hit,
            rejected=rejected, error=error, timeline_id=timeline_id)
        self._recent.append((time.monotonic(), obs.key,
                             float(latency_ms or 0.0),
                             int(obs.bytes_moved)))

    def record_rejection(self, body: dict, lane: str,
                         source: str = "admission") -> None:
        """One-shot tap for rejections that never reach the search
        boundary (wlm admission 429s at the REST layer)."""
        if not self.enabled:
            return
        key, shape, features = fingerprint(body, lane)
        self.sketch.record(key, shape, features, rejected=True)
        self._recent.append((time.monotonic(), key, 0.0, 0))

    # -- read side --

    def _windowed_entries(self, window_s: float) -> List[dict]:
        cutoff = time.monotonic() - float(window_s)
        agg: Dict[str, dict] = {}
        for t, key, lat, nbytes in list(self._recent):
            if t < cutoff:
                continue
            a = agg.setdefault(key, {"fingerprint": key, "count": 0,
                                     "latency_sum_ms": 0.0,
                                     "max_ms": 0.0, "bytes_moved": 0})
            a["count"] += 1
            a["latency_sum_ms"] = round(a["latency_sum_ms"] + lat, 3)
            a["max_ms"] = max(a["max_ms"], lat)
            a["bytes_moved"] += nbytes
        meta = self.sketch.meta_for(list(agg))
        out = []
        for a in agg.values():
            m = meta.get(a["fingerprint"])
            a["latency_mean_ms"] = round(
                a["latency_sum_ms"] / max(a["count"], 1), 3)
            if m is not None:
                a["shape"], a["features"], a["worst_timeline"] = m
            else:
                a["shape"] = "(evicted)"
            out.append(a)
        return out

    @staticmethod
    def _rank_key(by: str):
        if by == "count":
            return lambda d: (-d["count"], d["fingerprint"])
        if by == "bytes":
            return lambda d: (-d.get("bytes_moved", 0), d["fingerprint"])
        # latency: total burn (sum) — "which shape costs the fleet the
        # most wall time", the blame ordering remediation wants
        return lambda d: (-(d.get("latency") or {}).get("sum_ms", 0.0)
                          if "latency" in d
                          else -d.get("latency_sum_ms", 0.0),
                          d["fingerprint"])

    def top(self, by: str = "latency", n: int = 10,
            window_s: Optional[float] = None) -> List[dict]:
        """Top-N shapes. Without a window: lifetime sketch entries with
        derived percentiles. With a window: exact aggregates over the
        bounded recent-activity ring (count/latency/bytes), joined to
        sketch metadata."""
        if by not in TOP_BY:
            raise ValueError(f"unknown top_queries ranking [{by}] "
                             f"(one of {TOP_BY})")
        if window_s is not None:
            entries = self._windowed_entries(float(window_s))
        else:
            entries = [_derived(d)
                       for d in self.sketch.to_wire()["entries"]]
        entries.sort(key=self._rank_key(by))
        return entries[: max(int(n), 0)]

    def top_fingerprints(self, window_s: float, n: int = 5) -> List[dict]:
        """The SLO-burn enrichment payload: compact top-K active in the
        window, worst-timeline linked — bounded, label-safe (hashes and
        numbers only, plus the value-free shape)."""
        out = []
        for e in self.top(by="latency", n=n, window_s=window_s):
            out.append({"fingerprint": e["fingerprint"],
                        "shape": e.get("shape", ""),
                        "count": e["count"],
                        "latency_sum_ms": e.get("latency_sum_ms", 0.0),
                        "latency_mean_ms": e.get("latency_mean_ms", 0.0),
                        "bytes_moved": e.get("bytes_moved", 0),
                        "worst_timeline": e.get("worst_timeline", 0)})
        return out

    def prometheus_top(self, n: int = 10) -> List[dict]:
        """The bounded `/_metrics` export: top-N by count, labels are
        the shape hash only (OSL602: raw query text never reaches a
        label position)."""
        if not self.enabled:
            return []
        out = []
        for e in self.top(by="count", n=n):
            out.append({"fingerprint": e["fingerprint"],
                        "count": e["count"],
                        "latency_sum_ms": e["latency"]["sum_ms"],
                        "bytes_moved": e["bytes_moved"]})
        return out

    def to_wire(self, window_s: Optional[float] = None) -> dict:
        """Federation payload. Windowed wires carry exact ring
        aggregates in the same envelope (flagged `windowed`)."""
        if window_s is None:
            return self.sketch.to_wire()
        entries = self._windowed_entries(float(window_s))
        entries.sort(key=lambda d: (-d["count"], d["fingerprint"]))
        return {"capacity": self.capacity, "windowed": True,
                "window_s": float(window_s),
                "total_records": sum(e["count"] for e in entries),
                "full": False, "min_count": 0, "entries": entries}

    def stats(self) -> dict:
        """`_nodes/stats` "insights" block."""
        return {"enabled": self.enabled,
                "capacity": self.capacity,
                "entries": len(self.sketch),
                "total_records": self.sketch.total_records,
                "evictions": self.sketch.evictions,
                "window_capacity": self.window_capacity,
                "window_events": len(self._recent)}

    def reset(self) -> None:
        """Isolation hook for tests/bench cells (the METRICS.reset
        pattern)."""
        self.sketch.reset()
        self._recent.clear()


# process-default engine (one node per process, like METRICS/RECORDER)
INSIGHTS = QueryInsights()
