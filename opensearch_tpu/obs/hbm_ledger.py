"""HBM ledger: the single source of truth for device-memory accounting.

PR 3's telemetry observes the *time* domain and the flight recorder (PR 6)
the *event* domain; this module owns the *byte* domain. Every HBM tenant —
segment column pytrees (`index/segment.py:_build_device_arrays`),
partial-residency term arrays (`Segment.pruned_arrays`), fastpath aligned
postings and their filter-specialized copies, cached filter doc lists,
quality-tier views, nested-sort columns, per-shape compiled programs, and
the serving scheduler's in-flight batch workspaces — registers an
*attributed allocation* (tenant kind × segment × device × label) here, and
the circuit-breaker charge is DERIVED from the registration instead of
each module calling `breaker.add_estimate` ad hoc (oslint OSL506 enforces
that the ledger is the sole charge path).

Why: the north star (≥20× BM25 at fixed recall) is won in the byte domain.
ROADMAP item 1 (impact-quantized postings) claims a smaller HBM footprint
and fewer bytes moved per query; item 5's admission control needs real
HBM pressure signals. Neither is arguable without an attributed baseline —
"how many bytes does tenant X hold, and who moved what per query" must be
answerable before and after those PRs.

Design:

- **Attributed allocations.** `register()` returns an `Allocation` carrying
  (kind, nbytes, segment name/uid, device, label). Live allocations are
  indexed for the rollups `_nodes/stats` ("hbm"), `GET /_cat/segments`
  (per-segment device residency) and `scripts/hbm_report.py` serve.
- **Derived breaker charges.** A charged registration calls
  `breaker.add_estimate` on the breaker installed at charge time and
  remembers it, so the paired release always credits the same breaker
  even if a later `Node` swapped the process default (test isolation).
  The standing invariant — `sum(live charged bytes) == breaker.used` per
  breaker — is checked by `verify_breakers()` after every tier-1 test.
- **Release exactness.** `release()` is idempotent per allocation; an
  `owner` object ties release to a `weakref.finalize`, so a tenant GC'd
  without an explicit release still credits the breaker exactly once.
- **Peak tracking.** Total and per-kind peaks survive releases (the
  `_nodes/stats` "hbm" block; the allocator's own peak is the
  benchmark's `hbm_peak_gib`).
- **Silicon cross-check.** On a real device backend `check_device()`
  compares the ledger total against `device.memory_stats()["bytes_in_use"]`
  and triggers a flight-recorder anomaly dump (`hbm_drift`) past the
  threshold — the ledger audits itself against the hardware.

Flight-recorder linkage: registrations and releases on a request timeline
emit `hbm.build` / `hbm.evict` events, and a breaker trip emits
`hbm.breaker_trip`, so residency churn shows up on the same per-request
journal as scheduler and ladder events.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from typing import Any, Dict, List, Optional

from ..utils.metrics import METRICS
from . import flight_recorder as _fr

__all__ = ["Allocation", "HBMLedger", "LEDGER"]

# ledger-vs-allocator drift `check_device` lets pass whatever the device
# holds: XLA's scratch and program memory, which the ledger does not model
_DRIFT_FLOOR_BYTES = 64 << 20

# tenant kinds (docs/OBSERVABILITY.md "memory and cost"): free-form
# strings are accepted, but the known kinds keep dashboards stable
KINDS = (
    "segment_columns",      # Segment.device_arrays full pytree
    "impact_postings",      # codec-v2 quantized impact planes (u8/u16)
    "block_max",            # codec-v2 block-max sidecars (host, advisory)
    "postings_tfs",         # f32 tf planes promoted back onto v2 segments
    "partial_columns",      # Segment.pruned_arrays per-field arrays
    "aligned_postings",     # fastpath AlignedPostings (docs + packed tfdl)
    "filtered_postings",    # filter-specialized aligned copies
    "filter_list",          # cached FilterList device doc lists
    "quality_tier",         # static-pruning view masks/doc lists
    "nested_sort",          # planes.nested_sort_plane: i32 ranks per (segment, field, path, mode)
    "sort_rank_plane",      # compiler prepare_sort: i32 ranks per (segment, field)
    "agg_bucket_plane",     # planes.date_bucket_plane: i32 bucket ids
    "position_planes",      # a text field's (doc, pos) planes, with the segment
    "phrase_pairs",         # match_phrase_prefix unions merged on the host
    "mesh_postings",        # SPMD stacked per-shard postings/pairs
    "mesh_columns",         # SPMD stacked agg columns/ordinals/masks
    "program",              # compiled-program footprints (advisory)
    "batch_workspace",      # scheduler in-flight batch output buffers
)


class Allocation:
    """One live attributed device-memory tenant."""

    __slots__ = ("aid", "kind", "nbytes", "segment", "seg_uid", "device",
                 "label", "charged", "breaker", "live", "evictor")

    def __init__(self, aid: int, kind: str, nbytes: int, segment: str,
                 seg_uid: Optional[int], device: str, label: str,
                 breaker, evictor=None) -> None:
        self.aid = aid
        self.kind = kind
        self.nbytes = int(nbytes)
        self.segment = segment
        self.seg_uid = seg_uid
        self.device = device
        self.label = label
        self.breaker = breaker        # breaker CHARGED at register time
        self.charged = breaker is not None
        self.live = True
        # weak callable releasing this tenant's residency under memory
        # pressure (Segment.evict_device); None = not evictable
        self.evictor = evictor


def _device_key(device) -> str:
    if device is None:
        return "default"
    return str(device)


class HBMLedger:
    """Thread-safe attributed-allocation table + derived breaker charges.

    One per process (module singleton `LEDGER`), like TRACER / METRICS /
    RECORDER — one node per process is the deployment reality; multi-node
    tests share the table (allocations carry their own breaker refs, so
    per-node budgets stay exact)."""

    def __init__(self) -> None:
        # RLock: a weakref finalizer (-> _release_id) can fire at any
        # allocation point, including inside our own locked sections on
        # the same thread — a plain Lock would self-deadlock there
        self._lock = threading.RLock()
        self._breaker = None
        self._aid = itertools.count(1)
        self._allocs: Dict[int, Allocation] = {}
        self._by_kind: Dict[str, int] = {}
        self._peak_by_kind: Dict[str, int] = {}
        self._total = 0
        self._peak = 0
        # id(breaker) -> (breaker, charged bytes): the invariant ledger
        self._charged: Dict[int, list] = {}
        self.registrations = 0
        self.releases = 0
        self.breaker_trips = 0
        self.drift_checks = 0
        self.drift_dumps = 0
        self._last_drift_dump = 0.0    # monotonic; rate-limits dumps
        # LRU-by-segment-plane eviction under pressure: (seg_uid, device)
        # -> last-touch sequence. Writes are lock-free (GIL-atomic dict
        # assignment + thread-safe itertools.count) because touch() sits
        # on every query's device_arrays access.
        self._touch: Dict[tuple, int] = {}
        self._touch_seq = itertools.count(1)
        # live-allocation count per (seg_uid, device) plane group — O(1)
        # last-alloc detection on release (the alternative, scanning
        # _allocs, is quadratic over bulk drop_device/close churn) and
        # the failed-build guard for _touch cleanup
        self._group_refs: Dict[tuple, int] = {}
        self.pressure_evictions = 0

    # ---------------- wiring ----------------

    def set_breaker(self, breaker) -> None:
        """Install the breaker new charged registrations bill (the Node
        wires its fielddata breaker here; None disables charging)."""
        with self._lock:
            self._breaker = breaker

    @property
    def breaker(self):
        return self._breaker

    # ---------------- the write path ----------------

    def touch(self, segment, device=None) -> None:
        """Record query-time use of one segment's device residency — the
        recency signal LRU pressure eviction orders by. Lock-free (hot
        path): GIL-atomic dict write + thread-safe counter."""
        uid = getattr(segment, "uid", None)
        if uid is None:
            return
        # GIL-atomic single dict store + itertools.count (thread-safe in
        # CPython); readers (_evict_lru) snapshot under the ledger lock
        # and tolerate a stale recency value by design
        self._touch[(uid, _device_key(device))] = next(self._touch_seq)  # oslint: disable=OSL703 -- documented lock-free hot path

    def _evict_lru(self, breaker, exclude_uid) -> bool:
        """Evict the least-recently-used evictable segment-plane group
        charged to `breaker` (skipping `exclude_uid`, the tenant being
        built). Returns True when a group's evictor actually released
        residency. Caller holds the ledger lock (RLock — the evictor's
        releases re-enter it). Known coarseness: the victim is chosen
        per (segment, device) group but Segment.evict_device drops the
        segment's residency on EVERY device, so on multi-device hosts a
        pressure event also evicts the segment's other-device planes
        (and `bytes` below records only the chosen group's share)."""
        groups: Dict[tuple, list] = {}
        for a in list(self._allocs.values()):       # a snapshot, as there
            if a.evictor is None or a.breaker is not breaker:
                continue
            if a.seg_uid is None or a.seg_uid == exclude_uid:
                continue
            groups.setdefault((a.seg_uid, a.device), []).append(a)
        # oldest-touch first; never-touched groups (built, never queried)
        # are the coldest of all
        order = sorted(groups, key=lambda k: (self._touch.get(k, 0), k[0]))
        for key in order:
            allocs = groups[key]
            evictor = None
            for a in allocs:
                evictor = a.evictor() if a.evictor is not None else None
                if evictor is not None:
                    break
            if evictor is None:
                # owner GC'd mid-flight: its finalizers release the bytes
                continue
            freed = sum(a.nbytes for a in allocs)
            if not evictor():
                continue            # owner busy building: try the next
            self.pressure_evictions += 1
            self._touch.pop(key, None)
            if METRICS.enabled:
                METRICS.counter("hbm.pressure_evictions").inc()
            if _fr.RECORDER.enabled:
                tl = _fr.current()
                if tl:
                    _fr.RECORDER.record(
                        tl, "hbm.evict_pressure", segment=allocs[0].segment,
                        bytes=freed, device=allocs[0].device)
            return True
        return False

    def register(self, kind: str, nbytes: int, *, owner=None, segment=None,
                 device=None, label: str = "",
                 charge: bool = True, evictor=None) -> Allocation:
        """Record one attributed allocation and derive its breaker charge.

        `owner`: when given, a weakref finalizer releases the allocation
        at the owner's GC (explicit `release()` earlier is fine — release
        is idempotent per allocation). `segment` may be a Segment-like
        object (name/uid extracted) or a plain string. `charge=False`
        registers an advisory tenant (tracked, never billed — compiled
        program footprints whose true HBM cost XLA owns). `evictor`: a
        bound method (held weakly) that releases this tenant's residency
        on demand — registrations carrying one become candidates for
        LRU pressure eviction.

        An over-budget charged registration first tries to make room by
        evicting least-recently-used evictable segment planes charged to
        the same breaker (ROADMAP item 2: a 1M+ doc index must LOAD
        under a fixed budget, not fail); only when nothing evictable
        remains does the breaker's CircuitBreakingException propagate —
        nothing is recorded in that case."""
        seg_name = ""
        seg_uid = None
        if segment is not None:
            if isinstance(segment, str):
                seg_name = segment
            else:
                seg_name = getattr(segment, "name", "") or ""
                seg_uid = getattr(segment, "uid", None)
        nbytes = int(nbytes)
        breaker = self._breaker if (charge and nbytes > 0) else None
        if evictor is not None and not isinstance(evictor, weakref.ref):
            evictor = (weakref.WeakMethod(evictor)
                       if hasattr(evictor, "__self__")
                       else weakref.ref(evictor))
        alloc = Allocation(next(self._aid), kind, nbytes, seg_name, seg_uid,
                           _device_key(device), label, breaker,
                           evictor=evictor)
        with self._lock:
            if breaker is not None:
                while True:
                    try:
                        # charge INSIDE the ledger lock: CircuitBreaker is
                        # not thread-safe (check-then-act + bare `used +=`),
                        # and the ledger is its sole mutator — serializing
                        # here is what makes the breaker↔ledger invariant
                        # exact under concurrency
                        breaker.add_estimate(nbytes,
                                             label or f"hbm[{kind}]")
                        break
                    except Exception:
                        # pressure path: drop the LRU evictable plane and
                        # retry; give up (and re-raise) when nothing is
                        # left to evict
                        if self._evict_lru(breaker, seg_uid):
                            continue
                        self.breaker_trips += 1
                        if METRICS.enabled:
                            METRICS.counter("hbm.breaker_trips").inc()
                        if _fr.RECORDER.enabled:
                            tl = _fr.current()
                            if tl:
                                _fr.RECORDER.record(tl, "hbm.breaker_trip",
                                                    tenant=kind,
                                                    bytes=nbytes,
                                                    label=label)
                        if seg_uid is not None and not self._group_refs.get(
                                (seg_uid, alloc.device)):
                            # the build's pre-registration touch
                            # (Segment.device_arrays) minted a recency
                            # key for a group that never got an
                            # allocation — without this, sustained
                            # nothing-evictable pressure leaks a _touch
                            # entry per failed build forever (release
                            # cleanup only fires for groups that lived)
                            self._touch.pop((seg_uid, alloc.device), None)
                        raise
            self._allocs[alloc.aid] = alloc
            if seg_uid is not None:
                gk = (seg_uid, alloc.device)
                self._group_refs[gk] = self._group_refs.get(gk, 0) + 1
            self._by_kind[kind] = self._by_kind.get(kind, 0) + nbytes
            self._peak_by_kind[kind] = max(
                self._peak_by_kind.get(kind, 0), self._by_kind[kind])
            self._total += nbytes
            self._peak = max(self._peak, self._total)
            self.registrations += 1
            if breaker is not None:
                ent = self._charged.setdefault(id(breaker), [breaker, 0])
                ent[1] += nbytes
            gauge_total = self._total
            gauge_kind = self._by_kind.get(kind, 0)
        if METRICS.enabled:
            METRICS.gauge("hbm.ledger.total_bytes").set(gauge_total)
            METRICS.gauge(f"hbm.ledger.{kind}.bytes").set(gauge_kind)
        if _fr.RECORDER.enabled:
            tl = _fr.current()
            if tl:
                _fr.RECORDER.record(tl, "hbm.build", tenant=kind,
                                    bytes=nbytes, segment=seg_name,
                                    label=label)
        if owner is not None:
            weakref.finalize(owner, self._release_id, alloc.aid)
        return alloc

    def release(self, alloc: Optional[Allocation]) -> None:
        """Release one allocation: subtract its bytes and credit the
        breaker it was charged to. Idempotent — the weakref backstop and
        an explicit release can both fire."""
        if alloc is None:
            return
        self._release_id(alloc.aid)

    def _release_id(self, aid: int) -> None:
        with self._lock:
            alloc = self._allocs.pop(aid, None)
            if alloc is None or not alloc.live:
                return
            alloc.live = False
            self._by_kind[alloc.kind] = \
                self._by_kind.get(alloc.kind, 0) - alloc.nbytes
            self._total -= alloc.nbytes
            self.releases += 1
            if alloc.seg_uid is not None:
                gk = (alloc.seg_uid, alloc.device)
                n = self._group_refs.get(gk, 1) - 1
                if n <= 0:
                    # last allocation of this (segment, device) plane
                    # group: drop its LRU recency key too, or merge/
                    # refresh churn (every merge mints a new uid) leaks
                    # _touch entries in the process-singleton forever
                    self._group_refs.pop(gk, None)
                    self._touch.pop(gk, None)
                else:
                    self._group_refs[gk] = n
            if alloc.breaker is not None:
                ent = self._charged.get(id(alloc.breaker))
                if ent is not None:
                    ent[1] -= alloc.nbytes
                    # charged allocations always have nbytes > 0, so a
                    # zero balance already means no live charges remain
                    if ent[1] <= 0:
                        del self._charged[id(alloc.breaker)]
                # credit inside the lock — the ledger is the breaker's
                # sole mutator (see register)
                alloc.breaker.release(alloc.nbytes)
            gauge_total = self._total
            gauge_kind = self._by_kind.get(alloc.kind, 0)
        if METRICS.enabled:
            METRICS.gauge("hbm.ledger.total_bytes").set(gauge_total)
            METRICS.gauge(f"hbm.ledger.{alloc.kind}.bytes").set(gauge_kind)
        if _fr.RECORDER.enabled:
            tl = _fr.current()
            if tl:
                _fr.RECORDER.record(tl, "hbm.evict", tenant=alloc.kind,
                                    bytes=alloc.nbytes,
                                    segment=alloc.segment,
                                    label=alloc.label)

    # ---------------- reads ----------------

    def total_bytes(self) -> int:
        with self._lock:
            return self._total

    def snapshot(self) -> dict:
        """Rollup for `_nodes/stats` "hbm" and scripts/hbm_report.py:
        totals, peaks, and per-tenant-kind bytes/peaks/counts."""
        with self._lock:
            counts: Dict[str, int] = {}
            charged = 0
            for a in list(self._allocs.values()):   # as below: a snapshot
                counts[a.kind] = counts.get(a.kind, 0) + 1
                if a.charged:
                    charged += a.nbytes
            tenants = {
                k: {"bytes": self._by_kind.get(k, 0),
                    "peak_bytes": self._peak_by_kind.get(k, 0),
                    "count": counts.get(k, 0)}
                for k in sorted(set(self._by_kind) | set(counts))
                if self._by_kind.get(k, 0) or counts.get(k, 0)
                or self._peak_by_kind.get(k, 0)}
            return {"total_bytes": self._total,
                    "peak_bytes": self._peak,
                    "charged_bytes": charged,
                    "allocations": len(self._allocs),
                    "registrations": self.registrations,
                    "releases": self.releases,
                    "breaker_trips": self.breaker_trips,
                    "pressure_evictions": self.pressure_evictions,
                    "tenants": tenants}

    def top_tenants(self, limit: int = 10) -> List[dict]:
        """Largest live allocations, for `scripts/hbm_report.py`."""
        with self._lock:
            allocs = sorted(self._allocs.values(),
                            key=lambda a: (-a.nbytes, a.aid))[:limit]
            return [{"kind": a.kind, "bytes": a.nbytes,
                     "segment": a.segment, "device": a.device,
                     "label": a.label} for a in allocs]

    def segment_residency(self) -> Dict[Any, dict]:
        """Per-segment device residency: keyed by segment uid when known
        (stable across same-named segments of different indices), else
        name — the `GET /_cat/segments` columns."""
        out: Dict[Any, dict] = {}
        with self._lock:
            # a snapshot: a weakref finalizer of an owner the collector
            # frees inside this loop releases its allocation on this very
            # thread (the lock is re-entrant) and would resize the dict
            for a in list(self._allocs.values()):
                if not a.segment and a.seg_uid is None:
                    continue
                key = a.seg_uid if a.seg_uid is not None else a.segment
                ent = out.setdefault(key, {"segment": a.segment,
                                           "total_bytes": 0, "kinds": {}})
                ent["total_bytes"] += a.nbytes
                ent["kinds"][a.kind] = ent["kinds"].get(a.kind, 0) + a.nbytes
        return out

    # ---------------- invariants + silicon cross-check ----------------

    def verify_breakers(self) -> List[str]:
        """The standing ledger↔breaker invariant: for every breaker with
        (ever-unreleased) charges, the sum of live charged bytes must
        equal `breaker.used`. Returns human-readable mismatches (empty =
        healthy); asserted after every tier-1 test by a conftest
        fixture."""
        problems: List[str] = []
        with self._lock:
            entries = [(b, n) for (b, n) in self._charged.values()]
        for breaker, ledger_bytes in entries:
            used = getattr(breaker, "used", None)
            if used is None:
                continue
            if int(used) != int(ledger_bytes):
                problems.append(
                    f"breaker[{getattr(breaker, 'name', '?')}] used="
                    f"{used} but ledger holds {ledger_bytes} charged "
                    f"bytes")
        return problems

    def check_device(self, device=None,
                     threshold: float = 0.25) -> Optional[dict]:
        """On real silicon, cross-check the ledger total against the
        device allocator (`device.memory_stats()["bytes_in_use"]`).
        Drift beyond `threshold` (fraction of bytes_in_use, floor 64 MiB
        — XLA holds scratch/program memory the ledger deliberately does
        not model) triggers a flight-recorder `hbm_drift` dump, rate
        limited to one per 60s: callers include every `_nodes/stats`
        poll, and sustained drift must not churn useful anomaly dumps
        out of the bounded store. Returns the comparison, or None when
        the backend exposes no stats (CPU)."""
        import time as _time

        import jax
        if device is None:
            devices = jax.devices()
            if not devices:
                return None
            device = devices[0]
        stats_fn = getattr(device, "memory_stats", None)
        if stats_fn is None:
            return None
        try:
            stats = stats_fn()
        except Exception:
            return None
        if not stats or "bytes_in_use" not in stats:
            return None
        in_use = int(stats["bytes_in_use"])
        ledger = self.total_bytes()
        drift = abs(in_use - ledger)
        limit = max(int(in_use * threshold), _DRIFT_FLOOR_BYTES)
        out = {"device": str(device), "bytes_in_use": in_use,
               "ledger_bytes": ledger, "drift_bytes": drift,
               "drift_limit": limit, "ok": drift <= limit}
        with self._lock:
            self.drift_checks += 1
        if not out["ok"]:
            now = _time.monotonic()
            with self._lock:
                dump = now - self._last_drift_dump >= 60.0
                if dump:
                    self._last_drift_dump = now
                    self.drift_dumps += 1
            if dump and _fr.RECORDER.enabled:
                _fr.RECORDER.trigger(
                    "hbm_drift", [_fr.current()] if _fr.current() else None,
                    note=f"ledger {ledger}B vs device {in_use}B "
                         f"(drift {drift}B > {limit}B)", force=True)
        return out

    # ---------------- test/bench isolation ----------------

    def reset(self) -> None:
        """Release every live allocation (crediting breakers) and zero
        the peaks — isolation hook for bench cells and tests, mirroring
        `MetricsRegistry.reset`. Owners' weakref finalizers firing later
        are no-ops (release is idempotent per allocation)."""
        with self._lock:
            aids = list(self._allocs)
        for aid in aids:
            self._release_id(aid)
        with self._lock:
            self._peak = self._total
            self._peak_by_kind = {k: v for k, v in self._by_kind.items()
                                  if v}
            self.registrations = 0
            self.releases = 0
            self.breaker_trips = 0
            self.pressure_evictions = 0
            self._touch = {}
            self._group_refs = {}


# process-default ledger (one node per process, like TRACER/METRICS)
LEDGER = HBMLedger()
