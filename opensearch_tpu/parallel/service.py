"""MeshSearchService: the SPMD mesh path wired into the Node's REST search.

When a multi-device mesh is available (real TPU pod slice, or the virtual
8-CPU-device test mesh), eligible term-group queries dispatch over
`parallel/spmd.py`'s distributed program instead of the host shard loop:
per-shard scoring runs SPMD over the `shard` mesh axis, collection stats
(df, N, sum_dl) psum over ICI (device-side DFS phase), and per-shard top-ks
merge with an all_gather — the reference's coordinator fan-out
(`action/search/TransportSearchAction.java`,
`action/search/SearchPhaseController.java`) without the transport layer.

Fallback contract: `try_search` returns None whenever the query shape or
index layout isn't mesh-ready (complex plans, multi-segment shards, window
too deep), and the Node falls back to the host loop — identical results
either way (asserted by tests/test_distributed.py).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..index.segment import next_pow2
from ..obs import flight_recorder as _fr
from ..search.agg_compiler import (coerce_agg_ranges, grid_agg_precision,
                                   hist_agg_interval, range_agg_spec)
from ..utils.metrics import METRICS
from ..utils.trace import TRACER
from .spmd import (INT32_SENTINEL, StackedPhrasePairs, StackedShardIndex,
                   build_distributed_bincount,
                   build_distributed_cardinality,
                   build_distributed_ddsketch,
                   build_distributed_geo_stat,
                   build_distributed_metrics,
                   build_distributed_pair_metrics, build_distributed_phrase,
                   build_distributed_range_counts,
                   build_distributed_range_metrics,
                   build_distributed_search, build_distributed_terms_agg,
                   build_distributed_weighted_avg, make_mesh)

MAX_WINDOW = 1024

# metric agg kinds the mesh can reduce with psum/pmin/pmax (plain
# {"field": ...} bodies only — anything fancier takes the host loop)
_MESH_METRICS = ("min", "max", "sum", "avg", "value_count", "stats")

# keyword `terms` aggs run as an exact device bincount + psum when the
# field's global ordinal space fits this cap (counts array is [QB, vpad])
MAX_TERMS_VOCAB = 8192

# phrase queries: max terms the mesh serves (host loop beyond), and the
# cap on the positional pair bucket (a stopword-anchored phrase on a huge
# shard would blow the scatter working set)
MAX_PHRASE_T = 8
MAX_PHRASE_BUCKET = 1 << 22

# histogram-family aggs: bin-count cap for the mesh bincount program (a
# pathological interval over a wide value range -> host loop) and the max
# `range` agg ranges served as per-range masked sums
MAX_MESH_BINS = 4096
MAX_MESH_RANGES = 16

# adjacency_matrix builds N + N(N-1)/2 device masks (one metric launch
# each) — quadratic, so the mesh serves small matrices only (host loop
# beyond; the reference's own default cap is 100 filters)
MAX_MESH_ADJ_FILTERS = 8


class _ByteLRU:
    """Byte-budgeted LRU over an OrderedDict: one eviction policy for every
    device/host cache the service keeps (stacked agg columns, global
    ordinals, filter masks). Keeps a running byte total so eviction is O(1)
    per evicted entry.

    `kind`: when set, every nonzero-byte entry is registered with the HBM
    ledger (obs/hbm_ledger.py) under that tenant kind — eviction and
    replacement release the allocation, so `_nodes/stats` "hbm" and the
    breaker-derived charges track the mesh's device caches exactly."""

    def __init__(self, max_bytes: int, kind: Optional[str] = None):
        import collections
        import threading
        self._od: "collections.OrderedDict" = collections.OrderedDict()
        self._bytes = 0
        self._max = max_bytes
        self._kind = kind
        # concurrent searches (HTTP threads with the serving scheduler
        # off, msearch's per-body fallback pool) race move_to_end/popitem
        # without this; the lock is uncontended in the scheduler-on
        # steady state where one dispatcher thread owns the mesh
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            hit = self._od.get(key)
            if hit is not None:
                self._od.move_to_end(key)
                return hit[0]
            return None

    def put(self, key, value, nbytes: int) -> None:
        from ..obs.hbm_ledger import LEDGER
        alloc = None
        if self._kind is not None and nbytes:
            # register BEFORE taking the LRU lock (the ledger may raise
            # the breaker's CircuitBreakingException on an over-budget
            # build — nothing is cached in that case)
            alloc = LEDGER.register(self._kind, nbytes,
                                    label=f"mesh-lru{key!r}"[:160])
        released = []
        with self._lock:
            old = self._od.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
                released.append(old[2])
            self._od[key] = (value, nbytes, alloc)
            self._bytes += nbytes
            while self._bytes > self._max and len(self._od) > 1:
                _k, (_v, nb, al) = self._od.popitem(last=False)
                self._bytes -= nb
                released.append(al)
        for al in released:
            LEDGER.release(al)

    def __len__(self) -> int:
        return len(self._od)


class MeshSearchService:
    def __init__(self, devices: Optional[list] = None):
        import jax
        self.devices = list(devices) if devices is not None else jax.devices()
        self._meshes: Dict[int, object] = {}
        self._stacked: Dict[Tuple[str, str], Tuple[int, StackedShardIndex]] = {}
        self._programs: Dict[Tuple, object] = {}
        self._metric_programs: Dict[Tuple, object] = {}
        self._terms_programs: Dict[Tuple, object] = {}
        self._phrase_programs: Dict[Tuple, object] = {}
        self._hist_programs: Dict[Tuple, object] = {}
        self._range_programs: Dict[Tuple, object] = {}
        self._pair_metrics_programs: Dict[Tuple, object] = {}
        self._range_metrics_programs: Dict[Tuple, object] = {}
        self._card_programs: Dict[Tuple, object] = {}
        self._card_hashes = _ByteLRU(64 << 20)
        self._ddsketch_programs: Dict[Tuple, object] = {}
        self._wavg_programs: Dict[Tuple, object] = {}
        self._geo_programs: Dict[Tuple, object] = {}
        # (index, field) -> (generation, arrays-or-None); device caches
        # carry an HBM-ledger tenant kind so residency is attributed and
        # breaker-charged through the ledger (host-side caches stay
        # untracked — they hold RAM, not HBM)
        self._stacked_cols = _ByteLRU(self._COLS_MAX_BYTES,
                                      kind="mesh_columns")
        # (index, field) -> (generation, (val_doc, val_ord, vocab, vpad)
        #                    -or-None); smaller caps for the r5 caches so
        #        the aggregate device budget stays bounded near the original
        #        1 GiB rather than quadrupling
        self._stacked_ords = _ByteLRU(self._COLS_MAX_BYTES // 4,
                                      kind="mesh_columns")
        # filter-combo key -> per-shard host masks / device stacked mask
        self._host_masks = _ByteLRU(self._COLS_MAX_BYTES // 4)
        self._dev_masks = _ByteLRU(self._COLS_MAX_BYTES // 4,
                                   kind="mesh_columns")
        # (index, field) -> (generation, StackedPhrasePairs-or-None)
        self._stacked_pairs = _ByteLRU(self._COLS_MAX_BYTES // 2,
                                       kind="mesh_postings")
        # (index, field, kind, interval, offset) ->
        #     (generation, (bins_dev, min_b, nb)-or-None)
        self._stacked_bins = _ByteLRU(self._COLS_MAX_BYTES // 4,
                                      kind="mesh_columns")
        # SPMD program invocations must not interleave: two concurrent
        # runs of a collective program cross-join their per-device
        # participants at the XLA rendezvous and deadlock (observed on
        # the CPU backend under scheduler-off concurrent REST traffic).
        # One launch at a time is also the physical truth — the chip
        # serializes programs; the serving scheduler makes this lock
        # uncontended (a single dispatcher thread owns the mesh).
        # Everything this lock may nest over (ledger, stats, metrics,
        # tracer) is committed in lock_order.json and ratcheted by
        # tier-1 — and OSL702 rejects holding it across a device sync,
        # which is the shape of the original deadlock
        import threading
        self._dispatch_lock = threading.Lock()
        # counter mutations can now come from several threads at once
        # (the scheduler's completion worker fetches batch N while the
        # dispatcher launches N+1, and direct request threads decline in
        # parallel) — a GIL-sized lock keeps the tallies exact
        self._stats_lock = threading.Lock()
        self.dispatched = 0      # searches served by the mesh
        self.launches = 0        # scoring-program invocations (group = 1)
        self.fallbacks = 0       # searches declined -> host loop
        self.filtered_dispatched = 0   # of dispatched: bool-with-filters
        self.terms_agg_dispatched = 0  # of dispatched: with a terms agg
        self.phrase_dispatched = 0     # of dispatched: match_phrase
        # WHY each declined search host-looped, by decline site — surfaced
        # in _nodes/stats so a dispatch share can't silently flatter: a
        # flat `fallbacks` total hides whether
        # the misses are benign (single-shard index) or a served shape
        # regressing (e.g. agg columns failing to stack)
        self.fallback_shapes: Dict[str, int] = {}

    def _fall(self, shape: str, n: int = 1) -> None:
        with self._stats_lock:
            self.fallbacks += n
            self.fallback_shapes[shape] = \
                self.fallback_shapes.get(shape, 0) + n
        # registry mirror: every decline site attributed by shape, so the
        # Prometheus exposition carries the same why-did-it-host-loop
        # breakdown _nodes/stats does
        METRICS.counter("mesh.fallbacks").inc(n)
        METRICS.counter(f"mesh.fallback.{shape}").inc(n)
        if _fr.RECORDER.enabled:
            tl = _fr.current()
            if tl:
                _fr.RECORDER.record(tl, "mesh.decline", shape=shape)

    # ---------------- caches ----------------

    def _mesh_for(self, n_shard: int):
        if n_shard > len(self.devices):
            return None
        m = self._meshes.get(n_shard)
        if m is None:
            m = make_mesh(n_replica=1, n_shard=n_shard,
                          devices=self.devices[:n_shard])
            self._meshes[n_shard] = m
        return m

    def _stacked_for(self, name: str, svc, field: str, segments
                     ) -> Optional[StackedShardIndex]:
        key = (name, field)
        cached = self._stacked.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        mesh = self._mesh_for(len(segments))
        if mesh is None:
            return None
        stacked = StackedShardIndex.build(segments, field, mesh)
        # attribute the stacked per-shard postings (the mesh's dominant
        # HBM tenant) to the ledger; a generation bump replaces the dict
        # entry and the old index's GC releases the charge
        from ..obs.hbm_ledger import LEDGER
        LEDGER.register(
            "mesh_postings",
            sum(int(getattr(a, "nbytes", 0)) for a in
                (stacked.starts, stacked.doc_ids, stacked.tfs,
                 stacked.dl, stacked.live)),
            owner=stacked, label=f"mesh-stacked[{name}][{field}]")
        self._stacked[key] = (svc.generation, stacked)
        return stacked

    def _program_for(self, mesh, bucket: int, ndocs_pad: int, k: int,
                     k1: float, b: float, filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, k, k1, b, filtered)
        fn = self._programs.get(key)
        if fn is None:
            fn = build_distributed_search(mesh, bucket=bucket,
                                          ndocs_pad=ndocs_pad, k=k,
                                          k1=k1, b=b, filtered=filtered)
            self._programs[key] = fn
        return fn

    def _metric_program_for(self, mesh, bucket: int, ndocs_pad: int,
                            k1: float, b: float, filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, k1, b, filtered)
        fn = self._metric_programs.get(key)
        if fn is None:
            fn = build_distributed_metrics(mesh, bucket=bucket,
                                           ndocs_pad=ndocs_pad, k1=k1, b=b,
                                           filtered=filtered)
            self._metric_programs[key] = fn
        return fn

    def _terms_program_for(self, mesh, bucket: int, ndocs_pad: int,
                           vpad: int, k1: float, b: float,
                           filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, vpad, k1, b, filtered)
        fn = self._terms_programs.get(key)
        if fn is None:
            fn = build_distributed_terms_agg(mesh, bucket=bucket,
                                             ndocs_pad=ndocs_pad, vpad=vpad,
                                             k1=k1, b=b, filtered=filtered)
            self._terms_programs[key] = fn
        return fn

    _COLS_MAX_BYTES = 1 << 30   # device budget for stacked agg columns

    def _pairs_for(self, name: str, svc, field: str, shard_segs, stacked,
                   mesh) -> Optional[StackedPhrasePairs]:
        """Stacked positional pair arrays for `field` (phrase program
        input), cached per generation incl. negative results (fields
        without positions decline once, not per query)."""
        key = ("pairs", name, field)
        cached = self._stacked_pairs.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        pairs = StackedPhrasePairs.build(shard_segs, field, stacked, mesh)
        self._stacked_pairs.put(key, (svc.generation, pairs),
                                pairs.nbytes if pairs is not None else 0)
        return pairs

    def _phrase_program_for(self, mesh, bucket: int, ndocs_pad: int,
                            k: int, n_terms: int, k1: float, b: float,
                            filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, k, n_terms, k1, b, filtered)
        fn = self._phrase_programs.get(key)
        if fn is None:
            fn = build_distributed_phrase(mesh, bucket=bucket,
                                          ndocs_pad=ndocs_pad, k=k,
                                          n_terms=n_terms, k1=k1, b=b,
                                          filtered=filtered)
            self._phrase_programs[key] = fn
        return fn

    def _hist_program_for(self, mesh, bucket: int, ndocs_pad: int,
                          nb: int, k1: float, b: float,
                          filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, nb, k1, b, filtered)
        fn = self._hist_programs.get(key)
        if fn is None:
            fn = build_distributed_bincount(mesh, bucket=bucket,
                                            ndocs_pad=ndocs_pad, nb=nb,
                                            k1=k1, b=b, filtered=filtered)
            self._hist_programs[key] = fn
        return fn

    def _range_program_for(self, mesh, bucket: int, ndocs_pad: int,
                           nr: int, k1: float, b: float,
                           filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, nr, k1, b, filtered)
        fn = self._range_programs.get(key)
        if fn is None:
            fn = build_distributed_range_counts(mesh, bucket=bucket,
                                                ndocs_pad=ndocs_pad, nr=nr,
                                                k1=k1, b=b,
                                                filtered=filtered)
            self._range_programs[key] = fn
        return fn

    def _geo_for(self, name: str, svc, field: str, shard_segs,
                 d_pad: int, mesh) -> Optional[tuple]:
        """Stacked geo lat/lon/presence [S, d_pad] sharded over the mesh;
        None when no segment has the geo column. Cached per generation."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = ("geo", name, field)
        cached = self._stacked_cols.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        if not any(field in seg.geo_cols
                   for segs in shard_segs for seg in segs):
            self._stacked_cols.put(key, (svc.generation, None), 0)
            return None
        S = len(shard_segs)
        lat = np.zeros((S, d_pad), np.float32)
        lon = np.zeros((S, d_pad), np.float32)
        pres = np.zeros((S, d_pad), np.float32)
        for si, segs in enumerate(shard_segs):
            off = 0
            for seg in segs:
                gc = seg.geo_cols.get(field)
                if gc is not None:
                    lat[si, off: off + seg.ndocs] = gc.lat
                    lon[si, off: off + seg.ndocs] = gc.lon
                    pres[si, off: off + seg.ndocs] = \
                        gc.present.astype(np.float32)
                off += seg.ndocs
        sh = NamedSharding(mesh, P("shard"))
        out = (jax.device_put(lat, sh), jax.device_put(lon, sh),  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
               jax.device_put(pres, sh))  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
        self._stacked_cols.put(key, (svc.generation, out),
                               lat.nbytes * 3)
        return out

    def _grid_for(self, name: str, svc, field: str, kind: str,
                  precision: int, shard_segs, d_pad: int, mesh
                  ) -> Optional[tuple]:
        """Stacked GLOBAL geo-grid cell ordinals [S, d_pad] (-1 = no
        value) + the cell-key vocab union — per-segment cell ords from
        the host grid cache remapped into one index-wide ordinal space,
        so the device bincount program buckets globally. Cached per
        generation."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..search.planes import geo_grid_cache

        key = ("grid", name, field, kind, precision)
        cached = self._stacked_cols.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        per_seg = [[geo_grid_cache(seg, field, kind, precision)
                    for seg in segs] for segs in shard_segs]
        return self._stack_global_ords(key, svc, per_seg, shard_segs,
                                       d_pad, mesh)

    def _stack_global_ords(self, key: tuple, svc, per_seg, shard_segs,
                           d_pad: int, mesh) -> Optional[tuple]:
        """Shared remap of per-segment (vocab, doc-major ords) pairs into
        one index-wide ordinal space, stacked [S, d_pad] and sharded (-1 =
        missing). Used by the geo grids and multi_terms; cached per
        generation including negative results."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        vocab = sorted({v for srow in per_seg for (vs, _o) in srow
                        for v in vs})
        if not vocab or len(vocab) > MAX_TERMS_VOCAB:
            self._stacked_cols.put(key, (svc.generation, None), 0)
            return None
        gord = {v: i for i, v in enumerate(vocab)}
        S = len(shard_segs)
        bins = np.full((S, d_pad), -1, np.int32)
        for si, segs in enumerate(shard_segs):
            off = 0
            for seg, (vs, ords) in zip(segs, per_seg[si]):
                remap = np.full(max(len(vs), 1) + 1, -1, np.int32)
                for li, v in enumerate(vs):
                    remap[li] = gord[v]
                local = ords[: seg.ndocs]
                bins[si, off: off + seg.ndocs] = np.where(
                    local >= 0, remap[np.minimum(local, len(vs))], -1)
                off += seg.ndocs
        sh = NamedSharding(mesh, P("shard"))
        out = (jax.device_put(bins, sh), vocab)  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
        self._stacked_cols.put(key, (svc.generation, out), bins.nbytes)
        return out

    def _mterms_for(self, name: str, svc, fields: tuple, an, shard_segs,
                    stats, d_pad: int, mesh) -> Optional[tuple]:
        """Stacked GLOBAL combined multi_terms ordinals [S, d_pad]
        (-1 = doc missing any source) + the key-tuple vocab union — the
        per-segment combined ords from the host cache remapped into one
        index-wide ordinal space. Cached per generation."""
        from ..search.planes import multi_terms_cache

        key = ("mterms", name, fields)
        cached = self._stacked_cols.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        per_seg = []
        for si, segs in enumerate(shard_segs):
            row = []
            for seg in segs:
                try:
                    row.append(multi_terms_cache(seg, stats[si], an,
                                                 fields))
                except Exception:
                    self._stacked_cols.put(key, (svc.generation, None), 0)
                    return None
            per_seg.append(row)
        return self._stack_global_ords(key, svc, per_seg, shard_segs,
                                       d_pad, mesh)

    def _composite_fields(self, an) -> tuple:
        return tuple(next(iter(src.values()))["terms"]["field"]
                     for src in an.body["sources"])

    def _composite_for(self, an, name: str, svc, shard_segs, stats,
                       d_pad: int, mesh) -> Optional[tuple]:
        """Stacked combined ordinals for a composite over single-valued
        keyword terms sources — the per-doc key tuple equals the
        multi_terms combined key, so the multi_terms per-segment cache
        feeds the shared global-ordinal stacker. Declines (host loop)
        when any source field is multi-valued anywhere: the host pages
        per-value there, and a min-ord mapping would silently drop
        values."""
        fields = self._composite_fields(an)
        key = ("composite-ok", name, fields)
        cached = self._stacked_cols.get(key)
        if cached is not None and cached[0] == svc.generation:
            ok = cached[1]
        else:
            # every source must resolve (through aliases, like the host
            # prepare does) to a SINGLE-valued keyword column present in
            # EVERY segment: the host emits zero buckets per segment
            # lacking the column, and a min-ord mapping of a multi-valued
            # field would silently drop values — both decline
            mp = stats[0].mappings
            resolved = tuple(mp.aliases.get(f, f) for f in fields)
            ok = True
            for segs in shard_segs:
                for seg in segs:
                    for f in resolved:
                        col = seg.keyword_cols.get(f)
                        if col is None or (len(col.ords) and int(np.max(
                                col.starts[1:] - col.starts[:-1])) > 1):
                            ok = False
            self._stacked_cols.put(key, (svc.generation, ok), 0)
        if not ok:
            return None
        return self._mterms_for(name, svc, fields, an, shard_segs, stats,
                                d_pad, mesh)

    def _resolve_filters_aggs(self, agg_nodes, shard_segs, stats) -> bool:
        """Resolve every `filters` agg's named clauses to cached per-shard
        masks (same machinery as the query-level guardrail filters).
        Returns False when any clause can't be masked (caller falls back);
        resolved (key, combo, masks) lists ride on the AggNode."""
        from ..search import agg_compiler as AC, plan as PL
        from ..search import query_dsl as dsl

        for an in (agg_nodes or []):
            if an.kind not in ("filters", "adjacency_matrix", "filter",
                               "missing"):
                continue
            if an.kind == "adjacency_matrix":
                raw = an.body.get("filters", {})
                items = [(k, raw[k]) for k in sorted(raw)]
            elif an.kind == "filter":
                items = [("_f", an.body)]
            elif an.kind == "missing":
                items = [("_f", {"exists": {"field": an.body["field"]}})]
            else:
                items = AC.filters_agg_items(an.body)
            nodes = []
            for fname, f in items:
                try:
                    lnode = PL.rewrite(dsl.parse_query(f), stats[0],
                                       scoring=False)
                except dsl.QueryParseError:
                    return False
                if not self._maskable(lnode):
                    return False
                nodes.append((fname, lnode))
            resolved = []
            if an.kind == "missing":
                # parity guard: the host missing aggregator recognizes
                # ONLY numeric/keyword columns (text/geo fields count all
                # docs as missing there), while the exists mask sees
                # text/geo presence — serve only fields that are
                # numeric/keyword-backed in EVERY segment
                mp = stats[0].mappings
                f = mp.aliases.get(an.body["field"], an.body["field"])
                for segs in shard_segs:
                    for seg in segs:
                        if f not in seg.numeric_cols \
                                and f not in seg.keyword_cols:
                            return False
                # the wrapper mask is NOT exists(field)
                fp = self._fmask_resolve(shard_segs, stats, [],
                                         [nodes[0][1]])
                if fp is None:
                    return False
                an._mesh_filters = [("_f", fp[0], fp[1])]
                continue
            combos = [(fname, [ln]) for fname, ln in nodes]
            if an.kind == "adjacency_matrix":
                # plus the pairwise intersections, host label order
                sep = an.body.get("separator", "&")
                for ai in range(len(nodes)):
                    for bi in range(ai + 1, len(nodes)):
                        combos.append((
                            f"{nodes[ai][0]}{sep}{nodes[bi][0]}",
                            [nodes[ai][1], nodes[bi][1]]))
            for fname, lns in combos:
                fp = self._fmask_resolve(shard_segs, stats, lns, [])
                if fp is None:
                    return False
                resolved.append((fname, fp[0], fp[1]))
            an._mesh_filters = resolved
        return True

    def _sig_background(self, name: str, svc, field: str, shard_segs
                        ) -> tuple:
        """significant_terms superset stats summed over every segment of
        every shard (segments WITHOUT the column still contribute their
        live docs — reference supersetSize semantics). Cached per
        generation; the host path computes the same per segment."""
        from ..search.planes import kw_doc_counts

        key = ("sigbg", name, field)
        cached = self._stacked_cols.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        bg: Dict[str, int] = {}
        bg_total = 0
        for segs in shard_segs:
            for seg in segs:
                bg_total += seg.live_count
                if field in seg.keyword_cols:
                    for k, c in kw_doc_counts(seg, field).items():
                        bg[k] = bg.get(k, 0) + c
        out = (bg, bg_total)
        self._stacked_cols.put(key, (svc.generation, out),
                               64 * max(len(bg), 1))
        return out

    def _geo_program_for(self, mesh, bucket: int, ndocs_pad: int,
                         k1: float, b: float, filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, k1, b, filtered)
        fn = self._geo_programs.get(key)
        if fn is None:
            fn = build_distributed_geo_stat(
                mesh, bucket=bucket, ndocs_pad=ndocs_pad, k1=k1, b=b,
                filtered=filtered)
            self._geo_programs[key] = fn
        return fn

    def _card_program_for(self, mesh, bucket: int, ndocs_pad: int,
                          keyword: bool, vpad: int, k1: float, b: float,
                          filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, keyword, vpad, k1, b, filtered)
        fn = self._card_programs.get(key)
        if fn is None:
            fn = build_distributed_cardinality(
                mesh, bucket=bucket, ndocs_pad=ndocs_pad, keyword=keyword,
                vpad=vpad, k1=k1, b=b, filtered=filtered)
            self._card_programs[key] = fn
        return fn

    def _ddsketch_program_for(self, mesh, bucket: int, ndocs_pad: int,
                              k1: float, b: float, filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, k1, b, filtered)
        fn = self._ddsketch_programs.get(key)
        if fn is None:
            fn = build_distributed_ddsketch(
                mesh, bucket=bucket, ndocs_pad=ndocs_pad, k1=k1, b=b,
                filtered=filtered)
            self._ddsketch_programs[key] = fn
        return fn

    def _wavg_program_for(self, mesh, bucket: int, ndocs_pad: int,
                          k1: float, b: float, filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, k1, b, filtered)
        fn = self._wavg_programs.get(key)
        if fn is None:
            fn = build_distributed_weighted_avg(
                mesh, bucket=bucket, ndocs_pad=ndocs_pad, k1=k1, b=b,
                filtered=filtered)
            self._wavg_programs[key] = fn
        return fn

    def _pair_metrics_program_for(self, mesh, bucket: int, ndocs_pad: int,
                                  vpad: int, k1: float, b: float,
                                  filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, vpad, k1, b, filtered)
        fn = self._pair_metrics_programs.get(key)
        if fn is None:
            fn = build_distributed_pair_metrics(
                mesh, bucket=bucket, ndocs_pad=ndocs_pad, vpad=vpad,
                k1=k1, b=b, filtered=filtered)
            self._pair_metrics_programs[key] = fn
        return fn

    def _range_metrics_program_for(self, mesh, bucket: int, ndocs_pad: int,
                                   nr: int, k1: float, b: float,
                                   filtered: bool = False):
        key = (id(mesh), bucket, ndocs_pad, nr, k1, b, filtered)
        fn = self._range_metrics_programs.get(key)
        if fn is None:
            fn = build_distributed_range_metrics(
                mesh, bucket=bucket, ndocs_pad=ndocs_pad, nr=nr,
                k1=k1, b=b, filtered=filtered)
            self._range_metrics_programs[key] = fn
        return fn

    def _bins_for(self, name: str, svc, an, shard_segs, d_pad: int, mesh
                  ) -> Optional[tuple]:
        """Host-precomputed per-doc GLOBAL bin ids for a histogram /
        fixed-interval date_histogram (-1 = no value), stacked and
        shard-sharded — the mesh analog of the host 'hist' bin compute,
        done in one vectorized pass per (field, interval, offset) and
        cached per generation. Returns (bins_dev, min_b, nb, interval,
        offset) or None (missing column / too many bins)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        field = an.body["field"]
        interval, offset = hist_agg_interval(an.kind, an.body)
        if interval <= 0:
            return None
        key = (name, field, an.kind, interval, offset)
        cached = self._stacked_bins.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        if not any(field in seg.numeric_cols
                   for segs in shard_segs for seg in segs):
            self._stacked_bins.put(key, (svc.generation, None), 0)
            return None
        S = len(shard_segs)
        raw = np.full((S, d_pad), np.iinfo(np.int64).min, np.int64)
        for si, segs in enumerate(shard_segs):
            off = 0
            for seg in segs:
                nc = seg.numeric_cols.get(field)
                if nc is not None:
                    if an.kind == "date_histogram":
                        # exact i64 floor-div — the host date path
                        # (`compiler._host_date_buckets`) is integer, and
                        # epoch-ms values exceed f32 precision
                        bins = np.floor_divide(
                            nc.values.astype(np.int64) - np.int64(offset),
                            np.int64(max(interval, 1)))
                    else:
                        # f32 arithmetic to MATCH the host 'hist' kernel
                        # bit-for-bit (it bins the f32 column on device)
                        bins = np.floor(
                            (nc.values.astype(np.float32)
                             - np.float32(offset)) / np.float32(interval)
                        ).astype(np.int64)
                    bins = np.where(nc.present, bins,
                                    np.iinfo(np.int64).min).astype(np.int64)
                    raw[si, off: off + seg.ndocs] = bins
                off += seg.ndocs
        present = raw > np.iinfo(np.int64).min
        if not present.any():
            self._stacked_bins.put(key, (svc.generation, None), 0)
            return None
        min_b = int(raw[present].min())
        nb = int(raw[present].max()) - min_b + 1
        if nb > MAX_MESH_BINS:
            self._stacked_bins.put(key, (svc.generation, None), 0)
            return None
        bins32 = np.where(present, raw - min_b, -1).astype(np.int32)
        dev = jax.device_put(bins32, NamedSharding(mesh, P("shard")))  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
        out = (dev, min_b, nb, interval, offset)
        self._stacked_bins.put(key, (svc.generation, out), bins32.nbytes)
        return out

    def _col_for(self, name: str, svc, field: str, shard_segs,
                 d_pad: int, mesh) -> Optional[tuple]:
        """Stacked numeric column + presence mask [S, d_pad] sharded over
        the mesh, in the SAME per-shard concatenated doc space as the
        stacked postings; None when no segment has the column. Cached
        (incl. negative results) per generation under a byte-bounded LRU."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (name, field)
        cached = self._stacked_cols.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        # cheap membership test BEFORE any allocation: declining a text/
        # missing field must not zero megabytes per request
        if not any(field in seg.numeric_cols
                   for segs in shard_segs for seg in segs):
            self._stacked_cols.put(key, (svc.generation, None), 0)
            return None
        S = len(shard_segs)
        col = np.zeros((S, d_pad), np.float32)
        pres = np.zeros((S, d_pad), np.float32)
        for si, segs in enumerate(shard_segs):
            off = 0
            for seg in segs:
                nc = seg.numeric_cols.get(field)
                if nc is not None:
                    col[si, off: off + seg.ndocs] = \
                        nc.values.astype(np.float32)
                    pres[si, off: off + seg.ndocs] = \
                        nc.present.astype(np.float32)
                off += seg.ndocs
        sharding = NamedSharding(mesh, P("shard"))
        out = (jax.device_put(col, sharding),  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
               jax.device_put(pres, sharding))  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
        # byte-bounded LRU so long-lived nodes aggregating over many
        # fields/indices can't pin device columns forever
        self._stacked_cols.put(key, (svc.generation, out),
                               col.nbytes + pres.nbytes)
        return out

    def _ord_for(self, name: str, svc, field: str, shard_segs, d_pad: int,
                 mesh) -> Optional[tuple]:
        """Stacked keyword GLOBAL-ordinal values for a `terms` agg:
        (val_doc i32[S, NV], val_ord i32[S, NV], vocab) where val_doc is the
        per-shard concatenated doc index of each flat keyword value and
        val_ord its ordinal in the index-wide sorted vocab union — the mesh
        analog of the reference's global ordinals build
        (GlobalOrdinalsBuilder). Cached per generation; None when the field
        has no keyword column or its vocab exceeds MAX_TERMS_VOCAB."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (name, field)
        cached = self._stacked_ords.get(key)
        if cached is not None and cached[0] == svc.generation:
            return cached[1]
        cols = [[seg.keyword_cols.get(field) for seg in segs]
                for segs in shard_segs]
        if not any(c is not None for cs in cols for c in cs):
            self._stacked_ords.put(key, (svc.generation, None), 0)
            return None
        vocab = sorted({v for cs in cols for c in cs if c is not None
                        for v in c.vocab})
        if len(vocab) > MAX_TERMS_VOCAB:
            self._stacked_ords.put(key, (svc.generation, None), 0)
            return None
        gord = {v: i for i, v in enumerate(vocab)}
        S = len(shard_segs)
        nv = max(max(sum(len(c.ords) for c in cs if c is not None)
                     for cs in cols), 1)
        nv_pad = next_pow2(nv, floor=8)
        val_doc = np.full((S, nv_pad), INT32_SENTINEL, np.int32)
        val_ord = np.zeros((S, nv_pad), np.int32)
        for si, (segs, cs) in enumerate(zip(shard_segs, cols)):
            off = 0      # doc offset of this segment within the shard
            pos = 0      # flat value write position
            for seg, c in zip(segs, cs):
                if c is not None and len(c.ords):
                    n = len(c.ords)
                    val_doc[si, pos: pos + n] = \
                        c.doc_of_value.astype(np.int32) + off
                    remap = np.array([gord[v] for v in c.vocab], np.int32)
                    val_ord[si, pos: pos + n] = remap[c.ords]
                    pos += n
                off += seg.ndocs
        sharding = NamedSharding(mesh, P("shard"))
        out = (jax.device_put(val_doc, sharding),  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
               jax.device_put(val_ord, sharding), vocab,  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
               next_pow2(len(vocab), floor=8))
        self._stacked_ords.put(key, (svc.generation, out),
                               val_doc.nbytes + val_ord.nbytes)
        return out

    def _fmask_resolve(self, shard_segs, stats, fnodes, notnodes
                       ) -> Optional[tuple]:
        """Resolve a bool query's filter/must_not clauses to per-segment
        cached masks (compiler filter-mask cache) and combine them into one
        per-shard host mask. Returns (combo_key, masks_by_shard) — the key
        is the sorted per-clause cache keys, each already encoding segment
        uid + live_gen + spec digest, so index mutations mint new keys —
        or None when any clause's mask is unavailable (caller falls back to
        the host loop). The AND-combine only runs on a combo-cache miss;
        repeated guardrail combos pay just the per-clause cache hits."""
        from ..search import compiler as C

        # pass 1: per-clause cache keys (masks come along from the
        # compiler's own cache; the per-body cost on a hit is ~zero)
        clause_keys = []
        clause_masks = []   # aligned [(si, seg, mask, positive), ...]
        for si, segs in enumerate(shard_segs):
            for seg in segs:
                for node, positive in ([(n, True) for n in fnodes]
                                       + [(n, False) for n in notnodes]):
                    mask, mkey, _spec, _local = C.filter_mask_for(
                        node, seg, stats[si])
                    if mask is None:
                        return None
                    clause_keys.append((mkey, positive))
                    clause_masks.append((si, seg, mask, positive))
        combo = tuple(sorted(clause_keys))
        cached = self._host_masks.get(combo)
        if cached is not None:
            return combo, cached
        masks_by_shard = [[np.ones(seg.ndocs, bool) for seg in segs]
                          for segs in shard_segs]
        seg_pos = [{id(seg): j for j, seg in enumerate(segs)}
                   for segs in shard_segs]
        for si, seg, mask, positive in clause_masks:
            m = np.asarray(mask[: seg.ndocs], bool)
            tgt = masks_by_shard[si][seg_pos[si][id(seg)]]
            tgt &= m if positive else ~m
        self._host_masks.put(combo, masks_by_shard,
                             sum(m.nbytes for ms in masks_by_shard
                                 for m in ms))
        return combo, masks_by_shard

    def _dev_mask_for(self, combo, masks_by_shard, shard_segs, d_pad: int,
                      mesh):
        """Device-resident stacked f32[S, d_pad] filter mask for a resolved
        combo (shard-sharded); built once and LRU-cached — the
        guardrail-filter reuse the reference gets from its query cache
        (`indices/IndicesQueryCache.java`), as device-resident masks. The
        host masks travel WITH the call (not re-read from a cache that may
        have evicted them between parse and run)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (combo, d_pad)
        cached = self._dev_masks.get(key)
        if cached is not None:
            return cached
        S = len(shard_segs)
        fmask = np.zeros((S, d_pad), np.float32)
        for si, (segs, masks) in enumerate(zip(shard_segs, masks_by_shard)):
            off = 0
            for seg, m in zip(segs, masks):
                fmask[si, off: off + seg.ndocs] = m.astype(np.float32)
                off += seg.ndocs
        out = jax.device_put(fmask, NamedSharding(mesh, P("shard")))  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
        self._dev_masks.put(key, out, fmask.nbytes)
        return out

    # ---------------- dispatch ----------------

    def try_search(self, name: str, svc, body: dict) -> Optional[dict]:
        """One index, one term-group query -> full search response via the
        mesh, or None to fall back to the host shard loop."""
        return self.try_msearch(name, svc, [body])[0]

    def try_msearch(self, name: str, svc, bodies) -> list:
        """Synchronous msearch through the SPMD mesh: launch + fetch
        back-to-back (see `launch_msearch` for the split)."""
        return self.launch_msearch(name, svc, bodies).fetch()

    def launch_msearch(self, name: str, svc, bodies) -> "LaunchHandle":
        """A BATCH of search bodies over one index through the SPMD mesh:
        eligible bodies group by (similarity, window class) and run as ONE
        program invocation each — the query axis of the distributed
        program is the batch (replica-sharded on a pod), so an msearch of
        N term-group queries pays one dispatch, one DFS psum, and one
        all_gather merge for the whole group. Ineligible bodies come back
        as None for the host loop. Served shapes: scoring term groups
        (term/terms/match, any minimum_should_match) and filter-context
        groups (`terms`, constant score); multi-segment and empty shards;
        windows to MAX_WINDOW.

        LAUNCH stage: parse/eligibility, program build, and every program
        invocation run here — invocations serialized under
        `_dispatch_lock` (concurrent collective invocations cross-join
        their XLA rendezvous participants and deadlock), which is
        RELEASED before any device sync. The returned handle's `fetch()`
        performs the one-`device_get`-per-group transfer plus
        coordinator-side result assembly and returns the per-body
        response list (None entries -> host loop)."""
        from ..search.launch import LaunchHandle
        from ..search import plan as PL
        from ..search import query_dsl as dsl
        from ..search.executor import (_global_stats_contexts,
                                       _norm_sort_specs, parse_aggs,
                                       _collect_named)

        out: list = [None] * len(bodies)
        searchers = svc.searchers
        # the mesh program earns its keep on SHARDED indices (per-shard
        # SPMD scoring + device DFS/merge); a single-shard index would pay
        # compile + dispatch overhead for zero parallelism
        if svc.meta.num_shards < 2:
            self._fall("single_shard", len(bodies))
            return LaunchHandle(
                lambda: self._mark_declined(bodies, out), kind="mesh")
        # a shard may hold any number of segments (incl. zero for routing
        # holes) — the stacked index concatenates them per shard
        # ALL segments, including fully-deleted ones: the host's Lucene
        # maxDoc stats (N, df) count their docs, so excluding them skews
        # mesh idf; their live mask already zeroes every match
        shard_segs = [list(s.engine.segments) for s in searchers]
        stats = _global_stats_contexts(searchers)
        ctx = stats[0]

        parsed = []  # (qi, lt, sort_specs, window, const_score, aggs, fkey)
        for qi, body in enumerate(bodies):
            try:
                query = dsl.parse_query(body.get("query"))
            except dsl.QueryParseError:
                self._fall("parse_error")
                continue
            if isinstance(query, dsl.HybridQuery):
                # hybrid fuses at the coordinator AFTER N independent
                # retrievals (search/fusion.py) — declined BEFORE rewrite
                # (the rewriter 400s on nested hybrid) with its own
                # attributed shape, never the flat query_shape bucket
                self._fall("query_hybrid")
                continue
            lroot = PL.rewrite(query, ctx, scoring=True)
            sort_specs = _norm_sort_specs(body)
            agg_nodes = parse_aggs(body.get("aggs",
                                            body.get("aggregations")))
            window = int(body.get("from", 0)) + int(body.get("size", 10))
            shape = self._eligible(lroot, sort_specs, agg_nodes,
                                   _collect_named(lroot), body, window)
            if shape is None:
                self._fall(self._host_loop_shape(body, agg_nodes))
                continue
            lt, fnodes, notnodes, qboost, msm_eff = shape
            fpair = None            # (combo_key, per-shard host masks)
            if fnodes or notnodes:
                fpair = self._fmask_resolve(shard_segs, stats, fnodes,
                                            notnodes)
                if fpair is None:
                    self._fall("filter_unmaskable")
                    continue
            const = (float(getattr(lt, "boost", 1.0) or 1.0) * qboost
                     if getattr(lt, "mode", None) == "filter" else 0.0)
            # `filters` aggs: resolve each named filter to cached masks
            # now (parse-time ctx); any unmaskable clause -> host loop.
            # The resolved list rides on the AggNode (fresh per request)
            if not self._resolve_filters_aggs(agg_nodes, shard_segs,
                                              stats):
                self._fall("filters_agg_unmaskable")
                continue
            parsed.append((qi, lt, sort_specs, max(window, 1), const,
                           agg_nodes or [], fpair, qboost, msm_eff))
        if not parsed:
            return LaunchHandle(
                lambda: self._mark_declined(bodies, out), kind="mesh")

        # group by program parameters: field (via the stacked index), sim,
        # the pow2 WINDOW CLASS — co-batching a size=10 body with a
        # from+size=1000 body would force K=1024 merge slots on everyone
        # and every distinct K is its own compiled program — and the filter
        # combo (one device mask argument serves the whole group; guardrail
        # filters repeat heavily so batching survives the split)
        groups: dict = {}
        for item in parsed:
            (qi, lt, sort_specs, window, const, aggs, fpair, qboost,
             msm_eff) = item
            sim = lt.sim
            k1 = float(sim.k1) if sim is not None else 1.2
            b_eff = (float(sim.b)
                     if sim is not None and lt.has_norms else 0.0)
            k_class = min(next_pow2(max(window, 16)), MAX_WINDOW)
            fkey = fpair[0] if fpair is not None else None
            is_phrase = isinstance(lt, PL.LPhrase)
            nt_key = len(lt.terms) if is_phrase else 0
            groups.setdefault((is_phrase, nt_key, lt.field, k1, b_eff,
                               k_class, fkey), []).append(item)
        # LAUNCH: every group's program invocation runs here, serialized
        # under the dispatch lock; each returns a fetch closure capturing
        # its unfetched device arrays. The lock is released before ANY
        # fetch — the whole point of the split (the pipelined dispatcher
        # launches batch N+1 while a completion worker fetches batch N)
        fetchers = []
        # the lock-wait is a first-class forensic signal: under the
        # serving scheduler it should be ~0 (one dispatcher owns the
        # mesh); a growing wait means direct traffic is contending with
        # the scheduler for program invocation
        t_lock = time.monotonic()
        self._dispatch_lock.acquire()
        try:
            lock_wait_ms = (time.monotonic() - t_lock) * 1000.0
            METRICS.histogram("mesh.dispatch_lock_wait").record(
                lock_wait_ms)
            progs0 = len(self._programs)
            for (is_phrase, nt_key, field, k1, b_eff, k_class,
                 _fkey), items in groups.items():
                with TRACER.span("mesh.dispatch_group", field=field,
                                 k_class=k_class, queries=len(items),
                                 phrase=is_phrase):
                    if is_phrase:
                        fg = self._launch_phrase_group(
                            name, svc, bodies, out, shard_segs, stats,
                            searchers, field, nt_key, k1, b_eff, k_class,
                            items)
                    else:
                        fg = self._launch_mesh_group(
                            name, svc, bodies, out, shard_segs, stats,
                            searchers, field, k1, b_eff, k_class, items)
                    if fg is not None:
                        fetchers.append(fg)
            # delta read under the lock: a concurrent launch's compiles
            # must not be misattributed to this launch's forensics
            new_programs = len(self._programs) - progs0
        finally:
            self._dispatch_lock.release()

        info = None
        if _fr.RECORDER.enabled:
            info = {"path": "mesh", "bodies": len(parsed),
                    "groups": len(fetchers),
                    "lock_wait_ms": round(lock_wait_ms, 3),
                    "new_programs": new_programs}
            tl = _fr.current()
            if tl:
                # direct (non-scheduler) path: the request thread owns
                # the ambient timeline — stamp the launch boundary here;
                # scheduler-path launches are stamped per entry by the
                # dispatcher using handle.info
                _fr.RECORDER.record(tl, "mesh.launch", **info)

        def _finish():
            t_fetch = time.monotonic()
            for fg in fetchers:
                with TRACER.span("mesh.fetch_group"):
                    fg()
            if _fr.RECORDER.enabled:
                tl = _fr.current()
                if tl:
                    _fr.RECORDER.record(
                        tl, "mesh.fetch", groups=len(fetchers),
                        fetch_ms=round(
                            (time.monotonic() - t_fetch) * 1000.0, 3))
            return self._mark_declined(bodies, out)

        return LaunchHandle(_finish, kind="mesh", info=info)

    def _mark_declined(self, bodies, out) -> list:
        """Tag every body this call declined so the caller's per-body retry
        skips the mesh instead of re-declining it (Node.search pops the
        tag) — one logical search counts at most one fallback."""
        for body, resp in zip(bodies, out):
            if resp is None and isinstance(body, dict):
                body["_mesh_declined"] = True
        return out

    def _launch_mesh_group(self, name, svc, bodies, out, shard_segs,
                           stats, searchers, field, k1, b_eff, k_class,
                           items):
        """LAUNCH stage of one term-group program batch: agg-column
        staging, program build, and every program invocation (scoring +
        per-agg reduces) — returns a fetch closure over the unfetched
        device arrays, or None when the whole group declined. Must not
        block on device results (oslint OSL504); the single `device_get`
        lives in the returned closure."""
        t0 = time.monotonic()
        stacked = self._stacked_for(name, svc, field, shard_segs)
        if stacked is None:
            self._fall("no_stacked_index", len(items))
            return
        S = len(shard_segs)
        mesh = self._mesh_for(S)
        if mesh is None:
            self._fall("no_mesh", len(items))
            return
        # every item in the group shares one filter combo (the group key)
        fpair = items[0][6]
        K = min(k_class, stacked.ndocs_pad)
        keep = []
        for it in items:
            if it[3] > K:
                # deeper page than the program's merged top-k capacity
                # (tiny shards): that body takes the host loop
                self._fall("deep_window")
                continue
            # aggs need their stacked columns (metric) or global-ordinal
            # values (terms); a missing/oversized one -> host loop
            agg_ok = True
            for an in it[5]:
                if an.kind in ("geohash_grid", "geotile_grid"):
                    got = self._grid_for(name, svc, an.body["field"],
                                         an.kind,
                                         grid_agg_precision(an.kind,
                                                            an.body),
                                         shard_segs, stacked.ndocs_pad,
                                         mesh)
                elif an.kind in ("terms", "significant_terms",
                                 "rare_terms"):
                    got = self._ord_for(name, svc, an.body["field"],
                                        shard_segs, stacked.ndocs_pad, mesh)
                    if an.kind == "significant_terms" and got is not None \
                            and not all(an.body["field"] in seg.keyword_cols
                                        for segs in shard_segs
                                        for seg in segs):
                        # host fg_total EXCLUDES matches in segments
                        # lacking the column (sig_missing partials);
                        # the mesh total is global — mixed presence
                        # takes the host loop to keep parity exact
                        got = None
                elif an.kind in ("histogram", "date_histogram"):
                    got = self._bins_for(name, svc, an, shard_segs,
                                         stacked.ndocs_pad, mesh)
                elif an.kind == "multi_terms":
                    got = self._mterms_for(
                        name, svc,
                        tuple(src["field"] for src in an.body["terms"]),
                        an, shard_segs, stats, stacked.ndocs_pad, mesh)
                elif an.kind == "composite":
                    got = self._composite_for(an, name, svc, shard_segs,
                                              stats, stacked.ndocs_pad,
                                              mesh)
                elif an.kind == "cardinality":
                    # keyword fields ride global ordinals, numeric the
                    # stacked column; neither -> host loop
                    got = (self._ord_for(name, svc, an.body["field"],
                                         shard_segs, stacked.ndocs_pad,
                                         mesh)
                           or self._col_for(name, svc, an.body["field"],
                                            shard_segs, stacked.ndocs_pad,
                                            mesh))
                elif an.kind in ("filters", "adjacency_matrix",
                                 "filter", "missing"):
                    got = getattr(an, "_mesh_filters", None)
                elif an.kind == "weighted_avg":
                    got = self._col_for(
                        name, svc, an.body["value"]["field"], shard_segs,
                        stacked.ndocs_pad, mesh) and self._col_for(
                        name, svc, an.body["weight"]["field"], shard_segs,
                        stacked.ndocs_pad, mesh)
                elif an.kind in ("geo_bounds", "geo_centroid"):
                    got = self._geo_for(name, svc, an.body["field"],
                                        shard_segs, stacked.ndocs_pad,
                                        mesh)
                else:
                    got = self._col_for(name, svc, an.body["field"],
                                        shard_segs, stacked.ndocs_pad, mesh)
                for sub in an.subs:
                    if got is None:
                        break
                    got = self._col_for(name, svc, sub.body["field"],
                                        shard_segs, stacked.ndocs_pad,
                                        mesh)
                if got is None:
                    agg_ok = False
                    break
            if not agg_ok:
                self._fall("agg_column")
                continue
            keep.append(it)
        items = keep
        if not items:
            return
        # pad the query axis to pow2 so batch size never mints new program
        # shapes (dummy slots: all rows -1 -> every score -inf)
        QB = next_pow2(len(items), floor=1)
        T_pad = max(next_pow2(len(it[1].terms), floor=1) for it in items)
        rows = np.full((S, QB, T_pad), -1, np.int32)
        boosts = np.zeros((QB, T_pad), np.float32)
        msm = np.ones(QB, np.float32)
        cscore = np.zeros(QB, np.float32)
        total_max = 1
        for bi, (qi, lt, sort_specs, window, const, aggs, _fk, qboost,
                 msm_eff) in enumerate(items):
            nt = len(lt.terms)
            # a wrapping bool's boost folds into the term weights: BM25 is
            # linear in the per-term weight, so boost*score == sum of
            # boost-scaled contributions (constant-score goes via cscore)
            boosts[bi, :nt] = lt.raw_boosts[:nt] * qboost
            msm[bi] = float(lt.msm) if msm_eff is None else float(msm_eff)
            cscore[bi] = const
            for si in range(S):
                tot = 0
                for ti, t in enumerate(lt.terms):
                    r = stacked.row(si, t)
                    rows[si, bi, ti] = r
                    tot += stacked.row_size(si, r)
                total_max = max(total_max, tot)
        bucket = next_pow2(total_max, floor=256)
        filtered = fpair is not None
        fmask = (self._dev_mask_for(fpair[0], fpair[1], shard_segs,
                                    stacked.ndocs_pad, mesh)
                 if filtered else None)
        fn = self._program_for(mesh, bucket, stacked.ndocs_pad, K, k1,
                               b_eff, filtered)
        # one scoring-program invocation serves the whole query group:
        # queries per launch is the serving scheduler's coalescing ratio
        # (`_nodes/stats` mesh block, `/_metrics`)
        self.launches += 1
        METRICS.counter("mesh.launches").inc()
        gdocs_b, gvals_b, totals_b = fn(stacked.tree(), rows, boosts, msm,
                                        cscore, fmask)
        import jax

        # metric aggs: one psum/pmin/pmax reduce per distinct field over
        # the whole batch (items without that agg just ignore its column);
        # terms aggs: one exact bincount+psum per distinct keyword field
        metric_fields = sorted({
            an.body["field"] for it in items for an in it[5]
            if an.kind not in ("terms", "histogram", "date_histogram",
                               "range", "cardinality", "percentiles",
                               "percentile_ranks",
                               "median_absolute_deviation",
                               "weighted_avg", "geo_bounds",
                               "geo_centroid", "significant_terms",
                               "rare_terms", "geohash_grid",
                               "geotile_grid", "filters", "date_range",
                               "multi_terms", "adjacency_matrix",
                               "composite", "filter", "missing")})
        terms_fields = sorted({an.body["field"] for it in items
                               for an in it[5]
                               if an.kind in ("terms", "significant_terms",
                                              "rare_terms")})
        metrics_by_field = {}
        if metric_fields:
            mfn = self._metric_program_for(mesh, bucket, stacked.ndocs_pad,
                                           k1, b_eff, filtered)
            for f in metric_fields:
                col, pres = self._col_for(name, svc, f, shard_segs,
                                          stacked.ndocs_pad, mesh)
                margs = (stacked.tree(), rows, boosts, msm, cscore, col,
                         pres) + ((fmask,) if filtered else ())
                metrics_by_field[f] = mfn(*margs)
        tcounts_by_field = {}
        tvocab_by_field = {}
        # (parent key, metric field) -> (i32[QB, nb] counts,
        #                                f32[QB, nb, 4] moments)
        tsub_results = {}

        def _launch_pair_subs(an, parent_key, vpad_b, pvd, pvo,
                              sub_results):
            """One pair-metrics launch per (bucket parent, metric field),
            shared by every body in the batch nesting that metric."""
            for s in an.subs:
                skey = (parent_key, s.body["field"])
                if skey in sub_results:
                    continue
                mcol, mpres = self._col_for(name, svc, s.body["field"],
                                            shard_segs, stacked.ndocs_pad,
                                            mesh)
                pmfn = self._pair_metrics_program_for(
                    mesh, bucket, stacked.ndocs_pad, vpad_b, k1, b_eff,
                    filtered)
                pmargs = (stacked.tree(), rows, boosts, msm, cscore,
                          pvd, pvo, mcol, mpres) \
                    + ((fmask,) if filtered else ())
                sub_results[skey] = pmfn(*pmargs)

        terms_subs = [an for it in items for an in it[5]
                      if an.kind in ("terms", "rare_terms") and an.subs]
        for f in terms_fields:
            val_doc, val_ord, vocab, vpad = self._ord_for(
                name, svc, f, shard_segs, stacked.ndocs_pad, mesh)
            tfn = self._terms_program_for(mesh, bucket, stacked.ndocs_pad,
                                          vpad, k1, b_eff, filtered)
            targs = (stacked.tree(), rows, boosts, msm, cscore, val_doc,
                     val_ord) + ((fmask,) if filtered else ())
            tcounts_by_field[f] = tfn(*targs)
            tvocab_by_field[f] = vocab
            for an in terms_subs:
                if an.body["field"] == f:
                    _launch_pair_subs(an, f, vpad, val_doc, val_ord,
                                      tsub_results)
        # histogram family: one bincount program per distinct
        # (field, interval, offset); range: per-range masked sums
        def _hist_key(an):
            # key on the PARSED (interval, offset) floats, via the same
            # shared resolver _bins_for uses: semantically equal aggs share
            # one device run, distinct aggs never alias one cache entry
            interval, offset = hist_agg_interval(an.kind, an.body)
            return (an.kind, an.body["field"], interval, offset)

        def _norm_ranges(an):
            # date_range coerces from/to (date math/formats -> ms) through
            # the shared host helper before bound construction; memoized
            # per AggNode (fresh per request) — attach and the sub-launch
            # loop re-enter this per body
            got = getattr(an, "_mesh_ranges", None)
            if got is None:
                got = coerce_agg_ranges(an.kind, an.body,
                                        an.body["field"],
                                        stats[0].mappings)
                an._mesh_ranges = got
            return got

        def _range_key(an):
            # bucket keys are part of the RESPONSE, so custom "key" labels
            # must be part of the cache key too
            _, _, rkeys, metas = range_agg_spec(_norm_ranges(an))
            return (an.kind, an.body["field"], tuple(rkeys),
                    tuple((m.get("from"), m.get("to")) for m in metas))

        # cardinality: shard-local HLL registers + pmax (bit-identical to
        # the host's per-segment registers merged by max)
        card_results = {}
        card_fields = sorted({an.body["field"] for it in items
                              for an in it[5] if an.kind == "cardinality"})
        for f in card_fields:
            got = self._ord_for(name, svc, f, shard_segs,
                                stacked.ndocs_pad, mesh)
            if got is not None:
                val_doc, val_ord, vocab, vpad = got
                # vocab hashes cached per generation (the O(vocab) python
                # crc32 loop must not run per request), byte-bounded like
                # every other per-(index, field) cache here
                from ..search.planes import crc32_vocab_hashes
                hkey = (name, f)
                hcached = self._card_hashes.get(hkey)
                if hcached is not None and hcached[0] == svc.generation:
                    hashes = hcached[1]
                else:
                    hashes = crc32_vocab_hashes(vocab, vpad)
                    self._card_hashes.put(hkey,
                                          (svc.generation, hashes),
                                          hashes.nbytes)
                cfn = self._card_program_for(
                    mesh, bucket, stacked.ndocs_pad, True, vpad, k1,
                    b_eff, filtered)
                cargs = (stacked.tree(), rows, boosts, msm, cscore,
                         val_doc, val_ord, hashes) \
                    + ((fmask,) if filtered else ())
            else:
                col, pres = self._col_for(name, svc, f, shard_segs,
                                          stacked.ndocs_pad, mesh)
                cfn = self._card_program_for(
                    mesh, bucket, stacked.ndocs_pad, False, 0, k1, b_eff,
                    filtered)
                cargs = (stacked.tree(), rows, boosts, msm, cscore, col,
                         pres) + ((fmask,) if filtered else ())
            card_results[f] = cfn(*cargs)

        # DDSketch histograms (percentiles + percentile_ranks +
        # median_absolute_deviation share one program run per field) and
        # weighted_avg moments
        dd_results = {}
        dd_fields = sorted({an.body["field"] for it in items
                            for an in it[5]
                            if an.kind in ("percentiles",
                                           "percentile_ranks",
                                           "median_absolute_deviation")})
        for f in dd_fields:
            col, pres = self._col_for(name, svc, f, shard_segs,
                                      stacked.ndocs_pad, mesh)
            dfn = self._ddsketch_program_for(mesh, bucket,
                                             stacked.ndocs_pad, k1, b_eff,
                                             filtered)
            dargs = (stacked.tree(), rows, boosts, msm, cscore, col,
                     pres) + ((fmask,) if filtered else ())
            dd_results[f] = dfn(*dargs)
        wavg_results = {}
        wavg_pairs = sorted({(an.body["value"]["field"],
                              an.body["weight"]["field"])
                             for it in items for an in it[5]
                             if an.kind == "weighted_avg"})
        for vf, wf in wavg_pairs:
            vcol, vpres = self._col_for(name, svc, vf, shard_segs,
                                        stacked.ndocs_pad, mesh)
            wcol, wpres = self._col_for(name, svc, wf, shard_segs,
                                        stacked.ndocs_pad, mesh)
            wfn = self._wavg_program_for(mesh, bucket, stacked.ndocs_pad,
                                         k1, b_eff, filtered)
            wargs = (stacked.tree(), rows, boosts, msm, cscore, vcol,
                     vpres, wcol, wpres) + ((fmask,) if filtered else ())
            wavg_results[(vf, wf)] = wfn(*wargs)

        # geo grids: bincount over stacked global cell ordinals (the hist
        # program), one run per (field, kind, precision)
        grid_results = {}

        def _grid_key(an):
            return (an.body["field"], an.kind,
                    grid_agg_precision(an.kind, an.body))

        for it in items:
            for an in it[5]:
                if an.kind not in ("geohash_grid", "geotile_grid"):
                    continue
                gk = _grid_key(an)
                if gk in grid_results:
                    continue
                bins_dev, gvocab = self._grid_for(
                    name, svc, gk[0], gk[1], gk[2], shard_segs,
                    stacked.ndocs_pad, mesh)
                nbp = next_pow2(max(len(gvocab), 1))
                gfn_ = self._hist_program_for(
                    mesh, bucket, stacked.ndocs_pad, nbp, k1, b_eff,
                    filtered)
                gargs_ = (stacked.tree(), rows, boosts, msm, cscore,
                          bins_dev) + ((fmask,) if filtered else ())
                grid_results[gk] = (gfn_(*gargs_), gvocab)

        # `filters` agg: one metric-program count per named clause mask
        # (col == pres == the mask, so m[0] counts matched docs in it)
        fagg_results = {}
        fsub_results = {}     # (combo, metric field) ->
        #                       (i32[QB] counts, f32[QB, 4] moments)
        for it in items:
            for an in it[5]:
                if an.kind not in ("filters", "adjacency_matrix",
                                   "filter", "missing"):
                    continue
                mfn = self._metric_program_for(
                    mesh, bucket, stacked.ndocs_pad, k1, b_eff, filtered)
                for fname, combo, masks in an._mesh_filters:
                    dev = self._dev_mask_for(combo, masks, shard_segs,
                                             stacked.ndocs_pad, mesh)
                    if combo not in fagg_results:
                        margs = (stacked.tree(), rows, boosts, msm,
                                 cscore, dev, dev) \
                            + ((fmask,) if filtered else ())
                        fagg_results[combo] = mfn(*margs)
                    # metric subs under a `filter` wrapper: presence
                    # composes with the wrapper's mask on device
                    for sub in an.subs:
                        skey = (combo, sub.body["field"])
                        if skey in fsub_results:
                            continue
                        scol, spres = self._col_for(
                            name, svc, sub.body["field"], shard_segs,
                            stacked.ndocs_pad, mesh)
                        sargs = (stacked.tree(), rows, boosts, msm,
                                 cscore, scol, spres * dev) \
                            + ((fmask,) if filtered else ())
                        fsub_results[skey] = mfn(*sargs)

        # multi_terms + composite: combined global ordinals through the
        # bincount (a composite's key tuple IS the multi_terms key)
        mterms_results = {}
        for it in items:
            for an in it[5]:
                if an.kind not in ("multi_terms", "composite"):
                    continue
                if an.kind == "composite":
                    mk = ("composite",) + self._composite_fields(an)
                    bins_dev, mvocab = self._composite_for(
                        an, name, svc, shard_segs, stats,
                        stacked.ndocs_pad, mesh)
                else:
                    mk = tuple(src["field"] for src in an.body["terms"])
                    bins_dev, mvocab = self._mterms_for(
                        name, svc, mk, an, shard_segs, stats,
                        stacked.ndocs_pad, mesh)
                if mk in mterms_results:
                    continue
                nbp = next_pow2(max(len(mvocab), 1))
                mfn_ = self._hist_program_for(
                    mesh, bucket, stacked.ndocs_pad, nbp, k1, b_eff,
                    filtered)
                margs_ = (stacked.tree(), rows, boosts, msm, cscore,
                          bins_dev) + ((fmask,) if filtered else ())
                mterms_results[mk] = (mfn_(*margs_), mvocab)

        geo_results = {}
        geo_fields = sorted({an.body["field"] for it in items
                             for an in it[5]
                             if an.kind in ("geo_bounds", "geo_centroid")})
        for f in geo_fields:
            glat, glon, gpres = self._geo_for(name, svc, f, shard_segs,
                                              stacked.ndocs_pad, mesh)
            gfn = self._geo_program_for(mesh, bucket, stacked.ndocs_pad,
                                        k1, b_eff, filtered)
            gargs = (stacked.tree(), rows, boosts, msm, cscore, glat,
                     glon, gpres) + ((fmask,) if filtered else ())
            geo_results[f] = gfn(*gargs)

        hist_results = {}
        hist_bins = {}        # hist key -> device bins (sub-agg pair input)
        hist_pairs = {}       # hist key -> (val_doc, val_ord) device pairs
        range_results = {}
        hsub_results = {}     # (hist key, metric field) -> [QB, nb, 5]
        rsub_results = {}     # (range key, metric field) -> [QB, nr, 5]
        for it in items:
            for an in it[5]:
                if an.kind in ("histogram", "date_histogram"):
                    hk = _hist_key(an)
                    if hk not in hist_results:
                        (bins_dev, min_b, nb, interval,
                         offset) = self._bins_for(name, svc, an, shard_segs,
                                                  stacked.ndocs_pad, mesh)
                        hfn = self._hist_program_for(
                            mesh, bucket, stacked.ndocs_pad, nb, k1, b_eff,
                            filtered)
                        hargs = (stacked.tree(), rows, boosts, msm, cscore,
                                 bins_dev) + ((fmask,) if filtered else ())
                        hist_results[hk] = (hfn(*hargs), min_b, nb,
                                            interval, offset)
                        hist_bins[hk] = bins_dev
                    if an.subs:
                        if hk not in hist_pairs:
                            # bin-id pairs reused by every metric sub
                            # under this histogram: (local doc, bin) with
                            # sentinel docs for unbinned slots
                            import jax.numpy as jnp
                            bins_dev = hist_bins[hk]
                            hist_pairs[hk] = (
                                jnp.where(
                                    bins_dev >= 0,
                                    jnp.arange(stacked.ndocs_pad,
                                               dtype=jnp.int32)[None, :],
                                    INT32_SENTINEL),
                                jnp.maximum(bins_dev, 0))
                        hvd, hvo = hist_pairs[hk]
                        _launch_pair_subs(an, hk, hist_results[hk][2],
                                          hvd, hvo, hsub_results)
                elif an.kind in ("range", "date_range"):
                    rk = _range_key(an)
                    needed_subs = [s for s in an.subs
                                   if (rk, s.body["field"])
                                   not in rsub_results]
                    if rk in range_results and not needed_subs:
                        continue
                    lows, highs, rkeys, metas = range_agg_spec(
                        _norm_ranges(an))
                    col, pres = self._col_for(name, svc, an.body["field"],
                                              shard_segs,
                                              stacked.ndocs_pad, mesh)
                    if rk not in range_results:
                        rfn = self._range_program_for(
                            mesh, bucket, stacked.ndocs_pad, len(rkeys),
                            k1, b_eff, filtered)
                        rargs = (stacked.tree(), rows, boosts, msm, cscore,
                                 col, pres, lows, highs) \
                            + ((fmask,) if filtered else ())
                        range_results[rk] = (rfn(*rargs), rkeys, metas)
                    for s in needed_subs:
                        mcol, mpres = self._col_for(
                            name, svc, s.body["field"], shard_segs,
                            stacked.ndocs_pad, mesh)
                        rmfn = self._range_metrics_program_for(
                            mesh, bucket, stacked.ndocs_pad, len(rkeys),
                            k1, b_eff, filtered)
                        rmargs = (stacked.tree(), rows, boosts, msm,
                                  cscore, col, pres, lows, highs, mcol,
                                  mpres) + ((fmask,) if filtered else ())
                        rsub_results[(rk, s.body["field"])] = rmfn(*rmargs)

        # unfetched device outputs, captured for the deferred fetch (the
        # tuple is the closure's only handle on them; names shadowed
        # below so the outer bindings can be dropped with the handle)
        _pending = (gdocs_b, gvals_b, totals_b, metrics_by_field,
                    tcounts_by_field, hist_results, range_results,
                    tsub_results, hsub_results, rsub_results, card_results,
                    dd_results, wavg_results, geo_results, grid_results,
                    fagg_results, mterms_results, fsub_results)

        def _fetch_group():
            # ONE device->host transfer for the whole group's outputs —
            # the same single-device_get discipline the synchronous path
            # always had, just moved to the fetch stage
            fetched = jax.device_get(_pending)
            (gdocs_b, gvals_b, totals_b, metrics_by_field,
             tcounts_by_field, hist_results, range_results,
             tsub_results, hsub_results, rsub_results,
             card_results, dd_results, wavg_results,
             geo_results, grid_results, fagg_results,
             mterms_results, fsub_results) = fetched

            # attach the globally-reduced agg partials to shard 0 (the values
            # are already psum'd across the mesh; the coordinator merge sees
            # exactly one partial per agg)
            def _stat_partial(cnt, m4):
                # the host metric partial shape (`_merge_stats` input): count,
                # sum, sumsq always; extrema only meaningful when count > 0
                cnt = float(cnt)
                return {"count": cnt, "sum": float(m4[0]),
                        "min": float(m4[1]) if cnt > 0 else float("inf"),
                        "max": float(m4[2]) if cnt > 0 else float("-inf"),
                        "sumsq": float(m4[3])}

            def _ordinal_partial(counts, vocab, subs_of=None):
                # shared ordinal-bucket partial shape (terms / rare_terms /
                # significant_terms / geo grids)
                return {vocab[o]: {"doc_count": int(c),
                                   "subs": subs_of(o) if subs_of else {}}
                        for o, c in enumerate(counts[: len(vocab)]) if c > 0}

            def _bucket_subs(an, sub_results, parent_key, bi, j):
                out = {}
                for s in an.subs:
                    cnts, m4 = sub_results[(parent_key, s.body["field"])]
                    out[s.name] = _stat_partial(cnts[bi][j], m4[bi][j])
                return out

            def attach_aggs(results, bi, aggs):
                for an in aggs:
                    if an.kind in ("histogram", "date_histogram"):
                        hk = _hist_key(an)
                        counts, min_b, _nb, interval, offset = hist_results[hk]
                        buckets = {min_b + j: {
                            "doc_count": int(c),
                            "subs": _bucket_subs(an, hsub_results, hk, bi, j)}
                            for j, c in enumerate(counts[bi]) if c > 0}
                        results[0].agg_partials[an.name] = [{
                            "buckets": buckets, "interval": interval,
                            "offset": offset}]
                        continue
                    if an.kind in ("range", "date_range"):
                        rk = _range_key(an)
                        counts, rkeys, metas = range_results[rk]
                        buckets = {key: {
                            "doc_count": int(counts[bi][ri]),
                            "meta": metas[ri],
                            "subs": _bucket_subs(an, rsub_results, rk, bi, ri)}
                            for ri, key in enumerate(rkeys)}
                        results[0].agg_partials[an.name] = [{
                            "buckets": buckets}]
                        continue
                    if an.kind in ("terms", "rare_terms"):
                        f = an.body["field"]
                        buckets = _ordinal_partial(
                            tcounts_by_field[f][bi], tvocab_by_field[f],
                            (lambda o, _a=an, _f=f: _bucket_subs(
                                _a, tsub_results, _f, bi, o))
                            if an.subs else None)
                        results[0].agg_partials[an.name] = [{"buckets":
                                                             buckets}]
                        continue
                    if an.kind in ("geohash_grid", "geotile_grid"):
                        counts, gvocab = grid_results[_grid_key(an)]
                        buckets = _ordinal_partial(counts[bi], gvocab)
                        results[0].agg_partials[an.name] = [{"buckets":
                                                             buckets}]
                        continue
                    if an.kind in ("multi_terms", "composite"):
                        mk = (("composite",) + self._composite_fields(an)
                              if an.kind == "composite"
                              else tuple(src["field"]
                                         for src in an.body["terms"]))
                        counts, mvocab = mterms_results[mk]
                        buckets = _ordinal_partial(counts[bi], mvocab)
                        results[0].agg_partials[an.name] = [{"buckets":
                                                             buckets}]
                        continue
                    if an.kind in ("filter", "missing"):
                        _fn, combo, _m = an._mesh_filters[0]
                        subs = {}
                        for sub in an.subs:
                            sc, sm4 = fsub_results[(combo, sub.body["field"])]
                            subs[sub.name] = _stat_partial(sc[bi], sm4[bi])
                        # doc_count rides the program's int32 count plane:
                        # exact past the 2^24 f32 ceiling, no rounding
                        results[0].agg_partials[an.name] = [{
                            "doc_count": int(fagg_results[combo][0][bi]),
                            "subs": subs}]
                        continue
                    if an.kind in ("filters", "adjacency_matrix"):
                        buckets = {
                            fname: {"doc_count":
                                    int(fagg_results[combo][0][bi]),
                                    "subs": {}}
                            for fname, combo, _m in an._mesh_filters}
                        results[0].agg_partials[an.name] = [{"buckets":
                                                             buckets}]
                        continue
                    if an.kind == "significant_terms":
                        f = an.body["field"]
                        buckets = _ordinal_partial(tcounts_by_field[f][bi],
                                                   tvocab_by_field[f])
                        bg, bg_total = self._sig_background(name, svc, f,
                                                            shard_segs)
                        results[0].agg_partials[an.name] = [{
                            "buckets": buckets, "bg": bg,
                            "fg_total": int(totals_b[bi]),
                            "bg_total": bg_total}]
                        continue
                    if an.kind == "cardinality":
                        results[0].agg_partials[an.name] = [{
                            "registers": card_results[an.body["field"]][bi]}]
                        continue
                    if an.kind == "percentiles":
                        from ..search.agg_compiler import DEFAULT_PERCENTS
                        percents = list(an.body.get("percents",
                                                    DEFAULT_PERCENTS))
                        results[0].agg_partials[an.name] = [{
                            "hist": dd_results[an.body["field"]][bi],
                            "percents": percents}]
                        continue
                    if an.kind == "percentile_ranks":
                        results[0].agg_partials[an.name] = [{
                            "hist": dd_results[an.body["field"]][bi],
                            "values": [float(v) for v in
                                       an.body.get("values", ())]}]
                        continue
                    if an.kind == "median_absolute_deviation":
                        results[0].agg_partials[an.name] = [{
                            "hist": dd_results[an.body["field"]][bi]}]
                        continue
                    if an.kind == "weighted_avg":
                        wv = wavg_results[(an.body["value"]["field"],
                                           an.body["weight"]["field"])][bi]
                        results[0].agg_partials[an.name] = [{
                            "vwsum": float(wv[0]), "wsum": float(wv[1]),
                            "count": float(wv[2])}]
                        continue
                    if an.kind in ("geo_bounds", "geo_centroid"):
                        g = geo_results[an.body["field"]][bi]
                        if an.kind == "geo_bounds":
                            results[0].agg_partials[an.name] = [{
                                "count": float(g[0]), "top": float(g[1]),
                                "bottom": float(g[2]), "left": float(g[3]),
                                "right": float(g[4])}]
                        else:
                            results[0].agg_partials[an.name] = [{
                                "count": float(g[0]), "slat": float(g[5]),
                                "slon": float(g[6])}]
                        continue
                    mc, m4 = metrics_by_field[an.body["field"]]
                    results[0].agg_partials[an.name] = [
                        _stat_partial(mc[bi], m4[bi])]

            self._emit_mesh_results(name, bodies, out, shard_segs, stats,
                                    searchers, stacked, items, gdocs_b,
                                    gvals_b, totals_b, t0,
                                    attach_aggs=attach_aggs)

        return _fetch_group


    def _emit_mesh_results(self, name, bodies, out, shard_segs, stats,
                           searchers, stacked, items, gdocs_b, gvals_b,
                           totals_b, t0, attach_aggs=None,
                           phrase=False) -> None:
        """Shared coordinator-side result assembly for every mesh program:
        decode global doc ids back to (shard, segment, local), build the
        candidate pool (host final selection keeps tie-breaks identical to
        the shard loop), attach agg partials via `attach_aggs`, count
        dispatch telemetry, and finish each body through the normal search
        epilogue."""
        from ..search.executor import (Candidate, ShardQueryResult,
                                       _finish_search, _host_sort_values)

        S = len(shard_segs)
        doc_base = np.asarray(stacked.doc_base)
        seg_bases = [np.cumsum([0] + ndocs[:-1])
                     for ndocs in stacked.seg_ndocs]
        for bi, (qi, lt, sort_specs, window, _const, aggs, _fk, qboost,
                 _msm_eff) in enumerate(items):
            gdocs = gdocs_b[bi]
            gvals = gvals_b[bi]
            total = int(totals_b[bi])
            results = [ShardQueryResult(shard=i,
                                        segments=list(shard_segs[i]))
                       for i in range(S)]
            finite = np.isfinite(gvals)
            results[0].total = total
            results[0].max_score = (float(gvals[finite].max())
                                    if total > 0 and finite.any()
                                    else -np.inf)
            for j in range(len(gdocs)):
                if not np.isfinite(gvals[j]) or gdocs[j] < 0:
                    continue
                si = int(np.searchsorted(doc_base, gdocs[j], "right") - 1)
                in_shard = int(gdocs[j] - doc_base[si])
                seg_ord = int(np.searchsorted(seg_bases[si], in_shard,
                                              "right") - 1)
                local = in_shard - int(seg_bases[si][seg_ord])
                seg = shard_segs[si][seg_ord]
                if local >= seg.ndocs:
                    continue
                sc = float(gvals[j])
                sort_vals, raw_vals = _host_sort_values(sort_specs, seg,
                                                        local, sc)
                results[si].candidates.append(
                    Candidate(si, seg_ord, local, sc, sort_vals, raw_vals))
            if attach_aggs is not None:
                attach_aggs(results, bi, aggs)
            for r in results:
                r.took_ms = (time.monotonic() - t0) * 1000.0
            # fetch-stage counters: taken on whichever thread completes
            # the request (completion worker vs direct callers), so the
            # tallies need the stats lock
            with self._stats_lock:
                self.dispatched += 1
                if phrase:
                    self.phrase_dispatched += 1
                if _fk is not None:
                    self.filtered_dispatched += 1
                if any(an.kind == "terms" for an in aggs):
                    self.terms_agg_dispatched += 1
            METRICS.counter("mesh.dispatched").inc()
            METRICS.histogram("mesh.dispatch").record(
                (time.monotonic() - t0) * 1000.0)
            body = dict(bodies[qi])
            body["_index_name"] = name
            out[qi] = _finish_search(searchers, results, body, stats,
                                     name, t0, [] if phrase else aggs)

    def _launch_phrase_group(self, name, svc, bodies, out, shard_segs,
                             stats, searchers, field, n_terms, k1, b_eff,
                             k_class, items):
        """LAUNCH stage of one match_phrase program batch: shard-local
        positional pair-join + BM25 pseudo-term scoring + all_gather merge
        (spmd.build_distributed_phrase). Returns a fetch closure over the
        unfetched device arrays, or None when the group declined."""
        import jax

        t0 = time.monotonic()
        stacked = self._stacked_for(name, svc, field, shard_segs)
        if stacked is None:
            self._fall("no_stacked_index", len(items))
            return
        S = len(shard_segs)
        mesh = self._mesh_for(S)
        if mesh is None:
            self._fall("no_mesh", len(items))
            return
        pairs = self._pairs_for(name, svc, field, shard_segs, stacked,
                                mesh)
        if pairs is None:         # field has no positional postings
            self._fall("no_positions", len(items))
            return
        fpair = items[0][6]
        K = min(k_class, stacked.ndocs_pad)
        keep = []
        for it in items:
            if it[3] > K:
                self._fall("deep_window")
                continue
            keep.append(it)
        items = keep
        if not items:
            return
        ctx = stats[0]
        QB = next_pow2(len(items), floor=1)
        rows = np.full((S, QB, n_terms), -1, np.int32)
        weights = np.zeros(QB, np.float32)
        slops = np.zeros(QB, np.float32)
        avgdl = np.full(QB, max(float(ctx.avgdl(field)), 1e-9), np.float32)
        max_pairs = 1
        for bi, (qi, lt, sort_specs, window, _const, _aggs, _fk, qboost,
                 _msm_eff) in enumerate(items):
            weights[bi] = float(lt.weight) * float(qboost)
            slops[bi] = float(lt.slop)
            for si in range(S):
                for ti, t in enumerate(lt.terms):
                    r = stacked.row(si, t)
                    rows[si, bi, ti] = r
                    max_pairs = max(max_pairs, pairs.row_size(si, r))
        bucket = next_pow2(max_pairs, floor=64)
        if bucket > MAX_PHRASE_BUCKET:
            self._fall("phrase_bucket_cap", len(items))
            return
        filtered = fpair is not None
        fmask = (self._dev_mask_for(fpair[0], fpair[1], shard_segs,
                                    stacked.ndocs_pad, mesh)
                 if filtered else None)
        fn = self._phrase_program_for(mesh, bucket, stacked.ndocs_pad, K,
                                      n_terms, k1, b_eff, filtered)
        args = (stacked.tree(), pairs.tree(), rows, weights, slops,
                avgdl) + ((fmask,) if filtered else ())
        self.launches += 1
        METRICS.counter("mesh.launches").inc()
        _pending = fn(*args)            # invocation NOW, sync deferred

        def _fetch_group():
            gdocs_b, gvals_b, totals_b = jax.device_get(_pending)
            self._emit_mesh_results(name, bodies, out, shard_segs, stats,
                                    searchers, stacked, items, gdocs_b,
                                    gvals_b, totals_b, t0, phrase=True)

        return _fetch_group

    # agg kinds that today ALWAYS host-loop (VERDICT weak #4: the honest
    # remaining-host-loop list must carry per-shape counters so a
    # mesh-share measurement can't silently flatter). A declined body
    # carrying one of these is attributed `agg_<kind>`, not the flat
    # `query_shape` bucket.
    _HOST_LOOP_AGGS = frozenset((
        "nested", "reverse_nested", "global", "top_hits",
        "scripted_metric", "matrix_stats", "ip_range",
        "auto_date_histogram", "sampler", "diversified_sampler",
        "multi_terms", "variable_width_histogram", "children", "parent",
        "geo_distance"))

    # body keys that statically force the host loop (checked first in
    # `_eligible`); attributing them beats lumping them into query_shape.
    # The truthiness split mirrors _eligible EXACTLY — a falsy-present
    # key (e.g. `"profile": false`) did NOT cause the decline and must
    # not be blamed for it
    _HOST_LOOP_KEYS_TRUTHY = ("knn", "rescore", "profile", "collapse",
                              "suggest", "terminate_after")
    _HOST_LOOP_KEYS_PRESENT = ("min_score", "search_after", "timeout")

    def _host_loop_shape(self, body: dict, agg_nodes) -> str:
        """Finer decline attribution for `_eligible`-rejected bodies:
        which statically-host-loop feature sent this search to the host
        loop. Falls back to the generic `query_shape` when the decline
        came from the query tree itself."""

        def walk(nodes):
            for an in nodes or []:
                if an.kind in self._HOST_LOOP_AGGS:
                    return f"agg_{an.kind}"
                got = walk(an.subs)
                if got:
                    return got
            return None

        hit = walk(agg_nodes)
        if hit:
            return hit
        for k in self._HOST_LOOP_KEYS_TRUTHY:
            if body.get(k):
                return f"body_{k}"
        # vector/hybrid retrieval families decline by QUERY kind, not a
        # body key: a pure-knn / neural_sparse / hybrid query must show
        # up attributed in fallback_shapes (ISSUE 15 satellite — a
        # vector flood the remediator can shed needs a name), never as
        # the flat query_shape bucket
        q = body.get("query")
        if isinstance(q, dict) and len(q) == 1:
            qk = next(iter(q))
            if qk in ("knn", "hybrid", "neural_sparse"):
                return f"query_{qk}"
        for k in self._HOST_LOOP_KEYS_PRESENT:
            if body.get(k) is not None:
                return f"body_{k}"
        for an in (agg_nodes or []):
            if an.pipelines:
                return "agg_pipeline"
            for s in an.subs:
                if s.subs or s.pipelines or s.kind not in _MESH_METRICS:
                    return "agg_deep_subagg"
        return "query_shape"

    def _eligible(self, lroot, sort_specs, agg_nodes, named_nodes, body,
                  window: int) -> Optional[tuple]:
        """Mesh-servable shapes: a single term group (scoring OR filter
        mode), optionally wrapped in a bool with mask-computable
        filter/must_not clauses, plain relevance order, metric or keyword
        `terms` aggregations. Returns (lt, filter_nodes, must_not_nodes,
        bool_boost) or None (-> host loop)."""
        from ..search import plan as PL
        from ..search.fastpath import MAX_T
        from ..ops import scoring as ops

        if body.get("knn") or body.get("rescore") or body.get("min_score") \
                is not None or body.get("profile") or body.get("collapse") \
                or body.get("suggest") or body.get("search_after") is not None \
                or body.get("explain") == "device_plan" \
                or body.get("terminate_after"):
            # terminate_after is a per-segment collection budget — only
            # the host shard loop can stop between segment programs
            return None
        if body.get("timeout") is not None:
            # a LIVE deadline budget needs the deadline-aware host loop
            # too (a mesh launch cannot stop mid-program); the reference
            # no-timeout sentinel (-1) parses to no budget and stays
            # mesh-eligible
            from ..utils.deadline import parse_timeout_s
            try:
                if parse_timeout_s(body.get("timeout")) is not None:
                    return None
            except ValueError:
                return None          # junk -> host loop raises the 400
        if named_nodes:
            return None
        # metric aggs reduce over the mesh (psum/pmin/pmax); keyword terms
        # aggs as an exact device bincount; anything else -> host loop.
        # r5: bucket parents may carry plain {field} METRIC sub-aggs —
        # per-bucket moments scatter on device (pair/range metrics
        # programs) exactly like the reference's nested collectors
        def _subs_ok(an):
            return all(s.kind in _MESH_METRICS
                       and set(s.body) == {"field"}
                       and not s.subs and not s.pipelines
                       for s in an.subs) and not an.pipelines

        for an in (agg_nodes or []):
            if an.subs and not (
                    an.kind in ("terms", "rare_terms", "histogram",
                                "date_histogram", "range", "date_range",
                                "filter", "missing")
                    and _subs_ok(an)):
                return None
            # r5: single `filter` wrapper — the clause becomes a device
            # mask (query-filter machinery); metric subs compose their
            # presence with it. `missing` is the same wrapper with a
            # negated exists mask
            if an.kind == "filter":
                continue
            if an.kind == "missing" and set(an.body) == {"field"}:
                continue
            if an.kind in _MESH_METRICS and set(an.body) == {"field"} \
                    and not an.subs:
                continue
            # r5: cardinality as shard-local HLL registers + pmax (the
            # registers ARE the mergeable form, bit-identical to host)
            if an.kind == "cardinality" and set(an.body) == {"field"}:
                continue
            # r5: sketch metrics — DDSketch histograms merge by addition
            # (psum), weighted_avg by summed moments
            if an.kind == "percentiles" and set(an.body) <= \
                    {"field", "percents", "keyed"}:
                continue
            if an.kind == "percentile_ranks" and set(an.body) <= \
                    {"field", "values", "keyed"}:
                continue
            if an.kind == "median_absolute_deviation" \
                    and set(an.body) == {"field"}:
                continue
            if an.kind == "weighted_avg" \
                    and set(an.body) <= {"value", "weight"} \
                    and set(an.body.get("value") or {}) == {"field"} \
                    and set(an.body.get("weight") or {}) == {"field"}:
                continue
            # r5: geo_bounds/geo_centroid — masked lat/lon extremes and
            # centroid moments, pmax/pmin/psum over the shard axis
            if an.kind in ("geo_bounds", "geo_centroid") \
                    and set(an.body) == {"field"}:
                continue
            # r5: significant_terms — foreground counts are the exact
            # terms bincount; background stats are static per field
            if an.kind == "significant_terms" and set(an.body) <= \
                    {"field", "size", "min_doc_count", "shard_size"} \
                    and not an.subs:
                continue
            # r5: `filters` agg — each named maskable clause becomes a
            # per-shard device mask; counts via the metric program
            if an.kind == "filters" and set(an.body) <= {"filters"} \
                    and 1 <= len(an.body.get("filters") or ()) \
                    <= MAX_MESH_RANGES and not an.subs:
                continue
            # r5: adjacency_matrix — singles + pairwise AND masks through
            # the same filter-mask machinery as the `filters` agg
            if an.kind == "adjacency_matrix" and set(an.body) <= \
                    {"filters", "separator"} \
                    and 1 <= len(an.body.get("filters") or {}) \
                    <= MAX_MESH_ADJ_FILTERS and not an.subs:
                continue
            # r5: rare_terms rides the same exact bincount (our host path
            # is exact, not bloom-approximated, so parity is exact too)
            if an.kind == "rare_terms" and set(an.body) <= \
                    {"field", "max_doc_count"}:
                continue
            # r5: geo grids — host-precomputed per-doc cell ordinals
            # through the same device bincount as histograms
            if an.kind in ("geohash_grid", "geotile_grid") \
                    and set(an.body) <= {"field", "precision", "size"} \
                    and not an.subs:
                continue
            if an.kind == "terms" and set(an.body) <= \
                    {"field", "size", "min_doc_count", "order"}:
                order = an.body.get("order", {"_count": "desc"})
                if isinstance(order, dict) and len(order) == 1 and \
                        next(iter(order)) in ("_count", "_key"):
                    continue
            # r5: histogram family as a device bincount over host-built
            # global bin ids; `range` as per-range masked sums (ranges
            # may overlap). Calendar date intervals -> host loop.
            if an.kind == "histogram" and set(an.body) <= \
                    {"field", "interval", "offset", "min_doc_count"} \
                    and float(an.body.get("interval", 0)) > 0:
                continue
            if an.kind == "date_histogram" \
                    and not an.body.get("calendar_interval") \
                    and set(an.body) <= {"field", "fixed_interval",
                                         "interval", "offset",
                                         "min_doc_count"}:
                continue
            if an.kind in ("range", "date_range") and set(an.body) <= \
                    {"field", "ranges", "keyed", "format"} \
                    and 1 <= len(an.body.get("ranges") or []) \
                    <= MAX_MESH_RANGES:
                continue
            # r5: composite over single-valued keyword terms sources —
            # per-doc combined key == the multi_terms combined ordinal,
            # so it rides the same stacker + bincount; paging (after/
            # size/order) happens in the shared finalize
            if an.kind == "composite" and set(an.body) <= \
                    {"sources", "size", "after"} \
                    and an.body.get("sources") and not an.subs:
                ok = True
                for src in an.body["sources"]:
                    if len(src) != 1:
                        ok = False
                        break
                    (nm, scfg), = src.items()
                    if set(scfg) != {"terms"} \
                            or "field" not in scfg["terms"] \
                            or set(scfg["terms"]) - {"field", "order"}:
                        ok = False
                        break
                if ok:
                    continue
                return None
            # r5: multi_terms — per-doc combined ordinals through the
            # same device bincount as the geo grids
            if an.kind == "multi_terms" and set(an.body) <= \
                    {"terms", "size", "min_doc_count", "order"} \
                    and len(an.body.get("terms") or []) >= 2 \
                    and all(set(src) == {"field"}
                            for src in an.body["terms"]) \
                    and not an.subs:
                order = an.body.get("order", {"_count": "desc"})
                if isinstance(order, dict) and len(order) == 1 and \
                        next(iter(order)) in ("_count", "_key"):
                    continue
                return None
            return None
        if window > MAX_WINDOW or (window < 1 and not agg_nodes):
            return None
        if sort_specs and not (len(sort_specs) == 1
                               and sort_specs[0]["field"] == "_score"
                               and sort_specs[0].get("order", "desc")
                               == "desc"):
            return None

        # unwrap a bool: one scoring clause + maskable filters/must_nots.
        # msm_eff: the program-level minimum term matches — 0 when the bool
        # makes its single should OPTIONAL (filter-context bool, compiler
        # msm=0: docs matching only the filters still hit, scoring 0.0)
        fnodes: list = []
        notnodes: list = []
        qboost = 1.0
        msm_eff = None           # None -> use the term group's own msm
        lt = lroot
        if isinstance(lroot, PL.LBool):
            if lroot.shoulds:
                if lroot.musts or len(lroot.shoulds) != 1 or lroot.msm > 1:
                    return None
                lt = lroot.shoulds[0]
                if lroot.msm == 0:
                    # optional should: only sound with real filters (the
                    # match set is the filter set) and a scoring group
                    # (constant-score cscore would stamp non-matching docs)
                    if not lroot.filters or getattr(lt, "mode", None) \
                            != "score":
                        return None
                    msm_eff = 0.0
            elif len(lroot.musts) == 1:
                lt = lroot.musts[0]
            else:
                return None
            fnodes = list(lroot.filters)
            notnodes = list(lroot.must_nots)
            qboost = float(lroot.boost or 1.0)
            if not all(self._maskable(n) for n in fnodes + notnodes):
                return None
        if isinstance(lt, PL.LPhrase):
            # plain/filtered match_phrase on the mesh: the positional
            # pair-join program (spmd.build_distributed_phrase). Span
            # family (ordered/gap_cost), prefix expansion, and agg
            # combinations take the host loop; a bool-wrapped phrase must
            # be the REQUIRED clause (msm_eff None).
            if agg_nodes or msm_eff is not None:
                return None
            if lt.prefix_last or lt.ordered or lt.gap_cost:
                return None
            if lt.sim is None or lt.sim.sim_id != ops.SIM_BM25:
                return None
            if not 2 <= len(lt.terms) <= MAX_PHRASE_T:
                return None
            return (lt, fnodes, notnodes, qboost, msm_eff)
        if not isinstance(lt, PL.LTerms):
            return None
        if lt.mode not in ("score", "filter"):
            return None
        if lt.mode == "score" and (lt.sim is None
                                   or lt.sim.sim_id != ops.SIM_BM25):
            return None
        nt = len(lt.terms)
        if nt < 1 or next_pow2(nt, floor=1) > MAX_T:
            return None
        if getattr(lt, "raw_boosts", None) is None:
            return None
        if lt.aux is not None and np.any(np.asarray(lt.aux)[:nt] != 0.0):
            return None
        return (lt, fnodes, notnodes, qboost, msm_eff)

    def _maskable(self, node) -> bool:
        """Filter-context clauses the mesh serves via cached dense masks
        (compiler filter-mask cache) — the common guardrail kinds. Unknown
        kinds decline to the host loop, never guess."""
        from ..search import plan as PL

        if isinstance(node, (PL.LRange, PL.LExists, PL.LMatchAll,
                             PL.LMatchNone, PL.LIds, PL.LExpandTerms)):
            return True
        if isinstance(node, PL.LTerms):
            return True
        if isinstance(node, PL.LConstScore):
            return self._maskable(node.child)
        if isinstance(node, PL.LBool):
            return all(self._maskable(c) for c in
                       node.musts + node.shoulds + node.must_nots
                       + node.filters)
        return False

    def stats(self) -> dict:
        return {"devices": len(self.devices), "dispatched": self.dispatched,
                "launches": self.launches,
                "fallbacks": self.fallbacks,
                "fallback_shapes": dict(self.fallback_shapes),
                "filtered_dispatched": self.filtered_dispatched,
                "terms_agg_dispatched": self.terms_agg_dispatched,
                "phrase_dispatched": self.phrase_dispatched,
                "stacked_indices": len(self._stacked)}
