"""Ingest observatory: the write-path mirror of the query-path telemetry.

Every layer of the write path — bulk accept (`rest/client.py`), ingest
pipelines, the engine writer buffer, refresh with per-stage build
attribution, segment merge + BP reorder, translog, replica write-through
— records into the ONE process registry (`utils/metrics.METRICS`) under
the `indexing.` prefix. This module owns the pieces they share:

- the enable flag (`enabled()` / `set_enabled()`);
- the build-stage collector (`stage_scope()` / `note_stage()`): a
  thread-local dict the segment builders and the merge drop wall-time
  attributions into (pack / spill / chunk_merge / quantize /
  device_promote) without threading a parameter through every call —
  `note_stage` is a near-no-op when no refresh is collecting;
- writer-buffer accounting (`buffer_delta`): process-total doc/byte
  gauges summed over every open engine, the write-pressure inputs the
  future defer-merges actuator reads (ROADMAP item 5);
- refresh-to-visible recording: each doc's accept time is stamped at
  writer-buffer append (`Engine.index_doc`) and the accept→searchable
  delta lands in a DDSketch at refresh publish — the honest "how stale
  is search" number, recorded vectorized (`record_many`) so a 64k-doc
  refresh costs one lock acquisition, not 64k;
- the `refresh_stall` flight-recorder trigger (env
  `OPENSEARCH_TPU_REFRESH_STALL_MS`);
- `local_parts` / `merge_parts` / `assemble_block`: the `_nodes/stats`
  `"indexing"` block built from registry wire parts — the SAME assembly
  serves one node and a fleet, so federation (cluster/distnode.py
  `indexing` op) sums counters and gauges and merges DDSketch wire
  forms bin-wise, then computes percentiles from the ONE merged sketch.
  Fleet percentiles are never averages of per-node percentiles.

docs/OBSERVABILITY.md "Ingest observatory" documents the metric and
stage catalogue; oslint OSL605 (devtools/oslint/ingest_obs_rules.py)
patrols the emission discipline inside `index/` + `ingest/` hot loops.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, List, Sequence

from ..utils.metrics import METRICS, merge_sketches, sketch_snapshot

__all__ = ["enabled", "set_enabled", "stage_scope", "note_stage",
           "buffer_delta", "record_refresh_to_visible", "refresh_stall_ms",
           "refresh_stall", "segment_nbytes", "local_parts", "merge_parts",
           "assemble_block", "reset_buffer_totals", "record_refresh",
           "record_merge", "record_flush", "record_translog_append",
           "record_pipeline", "record_bulk", "count", "doc_bytes",
           "record_replica_sync", "FLUSH_EVERY", "BYTES_SAMPLE"]

PREFIX = "indexing."

# refresh wall times past this threshold freeze a flight-recorder dump
# (reason "refresh_stall", cooldown-limited like other storm-shaped
# triggers)
DEFAULT_REFRESH_STALL_MS = 5_000.0

_enabled_lock = threading.Lock()
_enabled = True


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> bool:
    """Flip write-path instrumentation; returns the previous value.
    Engines keep stamping accept times either way (one monotonic read
    per doc — the stamp array must stay parallel to the buffer), but
    nothing is recorded while disabled."""
    global _enabled
    with _enabled_lock:
        prev = _enabled
        _enabled = bool(on)
    return prev


def refresh_stall_ms() -> float:
    return float(os.environ.get("OPENSEARCH_TPU_REFRESH_STALL_MS",
                                DEFAULT_REFRESH_STALL_MS))


# ---------------- build-stage attribution ----------------

_stage_state = threading.local()


@contextlib.contextmanager
def stage_scope():
    """Collect `note_stage` attributions emitted on THIS thread for the
    duration of the scope. Yields the stage->seconds dict. Reentrancy
    (a refresh inside a refresh) keeps the outer collector: attributions
    roll up to the outermost scope, matching how the refresh stage
    partition nests."""
    prev = getattr(_stage_state, "col", None)
    col = prev if prev is not None else {}
    _stage_state.col = col
    try:
        yield col
    finally:
        _stage_state.col = prev


def note_stage(stage: str, seconds: float) -> None:
    """Attribute `seconds` of build wall time to `stage`. No-op (one
    thread-local read) unless a `stage_scope` is active on this thread —
    the builders call this unconditionally; only a collecting refresh
    pays for it."""
    col = getattr(_stage_state, "col", None)
    if col is not None:
        col[stage] = col.get(stage, 0.0) + seconds


# ---------------- writer-buffer accounting ----------------

# per-doc accounting in Engine.index_doc is ONE int add (already
# serialized by the index write lock); byte estimation and the registry
# gauges/counter are folded in every FLUSH_EVERY docs and at refresh,
# sizing at most BYTES_SAMPLE docs sampled from the freshly-appended
# buffer tail and scaling to the fold. Bounded staleness (< FLUSH_EVERY
# docs) and the sampled estimate together buy back the ~10% bulk
# throughput that per-doc emission cost — even one extra Python call
# per accepted doc is measurable at 32 submit threads.
FLUSH_EVERY = 64
BYTES_SAMPLE = 8


def doc_bytes(source) -> int:
    """Cheap structural byte estimate for the writer-buffer gauge —
    O(#fields) over the top level, never a serialization of the doc.
    Called at fold time on a sample of the buffer tail, never per
    accepted doc."""
    est = 24
    for k, v in source.items():
        est += len(k) + 8
        if isinstance(v, str):
            est += len(v)
        elif isinstance(v, (list, tuple)):
            est += 8 * len(v)
    return est


_buf_lock = threading.Lock()
_buf_docs = 0
_buf_bytes = 0


def buffer_delta(docs: int, nbytes: int) -> None:
    """Fold a writer-buffer change (±docs, ±bytes) into the process-total
    gauges `indexing.buffer.docs` / `indexing.buffer.bytes`. Engines add
    per accepted doc and subtract their tracked totals at refresh, so
    the gauges stay consistent across enable toggles mid-buffer."""
    global _buf_docs, _buf_bytes
    with _buf_lock:
        _buf_docs = max(0, _buf_docs + int(docs))
        _buf_bytes = max(0, _buf_bytes + int(nbytes))
        d, b = _buf_docs, _buf_bytes
    METRICS.gauge("indexing.buffer.docs").set(d)
    METRICS.gauge("indexing.buffer.bytes").set(b)


def reset_buffer_totals() -> None:
    """Test/bench isolation: zero the process buffer totals (pairs with
    `MetricsRegistry.reset`, which drops the gauges themselves)."""
    global _buf_docs, _buf_bytes
    with _buf_lock:
        _buf_docs = 0
        _buf_bytes = 0


# ---------------- refresh-to-visible ----------------

def record_refresh_to_visible(index_name: str,
                              accept_stamps: Sequence[float],
                              now_mono: float) -> None:
    """Record accept→searchable deltas for one published refresh: the
    global sketch plus a per-index sketch (cardinality bounded by the
    index count, never the doc count). Vectorized — one `record_many`
    per sketch regardless of the refresh size."""
    if not accept_stamps:
        return
    import numpy as np
    deltas = (now_mono - np.asarray(accept_stamps, np.float64)) * 1000.0
    np.clip(deltas, 0.0, None, out=deltas)
    METRICS.histogram("indexing.refresh_to_visible_ms").record_many(deltas)
    if index_name:
        METRICS.histogram(
            f"indexing.index.{index_name}.refresh_to_visible_ms"
        ).record_many(deltas)


def refresh_stall(index_name: str, total_ms: float,
                  stages: Dict[str, float]) -> None:
    """Freeze a flight-recorder dump for a refresh that blew the stall
    threshold: one `refresh` timeline carrying the stage partition, then
    a cooldown-limited `refresh_stall` trigger."""
    METRICS.counter("indexing.refresh.stalls").inc()
    from .flight_recorder import RECORDER
    if not RECORDER.enabled:
        return
    tl = RECORDER.start("refresh", index=index_name or "_unnamed")
    if tl:
        RECORDER.record(tl, "refresh.stall", total_ms=round(total_ms, 3),
                        stall_threshold_ms=refresh_stall_ms(),
                        **{f"{k}_ms": round(v * 1000.0, 3)
                           for k, v in stages.items()})
        RECORDER.trigger(
            "refresh_stall", [tl],
            note=f"refresh of [{index_name or '_unnamed'}] took "
                 f"{total_ms:.0f}ms (threshold {refresh_stall_ms():.0f}ms)")


# ---------------- emission helpers ----------------
#
# The hot write-path modules (index/, ingest/ — oslint OSL605 scope) call
# ONE guarded helper per event instead of looping over registry lookups
# themselves; every bounded stage/name loop lives here in obs/ (exempt,
# like OSL505).

def record_refresh(index_name: str, ndocs: int, streamed: bool,
                   stamps, build_detail: Dict[str, float],
                   backlog: int) -> None:
    """Fold one published refresh into the registry: totals, the exact
    stage partition (collect/build/publish/merge from boundary stamps
    t0..t4), the builder's stage attributions, and the merge-pressure
    signals. Fires the `refresh_stall` dump past the threshold."""
    t0, t1, t2, t3, t4 = stamps
    total_ms = (t4 - t0) * 1000.0
    METRICS.counter("indexing.refresh.total").inc()
    METRICS.counter("indexing.refresh.docs").inc(int(ndocs))
    if streamed:
        METRICS.counter("indexing.refresh.stream_total").inc()
    METRICS.histogram("indexing.refresh.time_ms").record(total_ms)
    stages = {"collect": t1 - t0, "build": t2 - t1,
              "publish": t3 - t2, "merge": t4 - t3}
    for k, v in stages.items():
        METRICS.histogram(f"indexing.refresh.stage.{k}_ms").record(
            v * 1000.0)
    for k, v in build_detail.items():
        METRICS.histogram(f"indexing.refresh.build.{k}_ms").record(
            v * 1000.0)
    # write-pressure inputs (the defer-merges actuator's future diet):
    # the gauge is "now", the depth sketch is "how it's been" — the
    # merge-backlog burn SLO windows over the sketch
    METRICS.gauge("indexing.merge.backlog").set(int(backlog))
    METRICS.histogram("indexing.merge.backlog_depth").record(float(backlog))
    if total_ms >= refresh_stall_ms():
        refresh_stall(index_name, total_ms, stages)


def record_merge(n_inputs: int, input_docs: int, input_bytes: int,
                 merged, dur_s: float, reorder_s: float,
                 reordered: bool) -> None:
    """One TOP-LEVEL segment merge (nested child merges are part of their
    parent's numbers — merge.py only reports names without a '/')."""
    METRICS.counter("indexing.merge.total").inc()
    METRICS.counter("indexing.merge.input_segments").inc(int(n_inputs))
    METRICS.counter("indexing.merge.input_docs").inc(int(input_docs))
    METRICS.counter("indexing.merge.input_bytes").inc(int(input_bytes))
    METRICS.counter("indexing.merge.output_docs").inc(int(merged.ndocs))
    METRICS.counter("indexing.merge.output_bytes").inc(
        segment_nbytes(merged))
    METRICS.histogram("indexing.merge.time_ms").record(dur_s * 1000.0)
    if reordered:
        METRICS.counter("indexing.merge.reorder_total").inc()
        METRICS.histogram("indexing.merge.reorder_ms").record(
            reorder_s * 1000.0)


def record_flush(dur_ms: float, translog_age_s: float) -> None:
    METRICS.counter("indexing.flush.total").inc()
    METRICS.histogram("indexing.flush.time_ms").record(dur_ms)
    METRICS.gauge("indexing.translog.age_s").set(float(translog_age_s))


def record_translog_append(nbytes: int) -> None:
    METRICS.counter("indexing.translog.ops").inc()
    METRICS.counter("indexing.translog.bytes").inc(int(nbytes))


def record_pipeline(dur_ms: float, dropped: bool) -> None:
    METRICS.counter("indexing.pipeline.docs").inc()
    if dropped:
        METRICS.counter("indexing.pipeline.dropped").inc()
    METRICS.histogram("indexing.pipeline.time_ms").record(dur_ms)


def count(name: str, n: int = 1) -> None:
    """Guarded one-off counter bump for swallowed-exception audit sites
    (`indexing.{stage}.failed` family) — callers pass the full metric
    name; the helper keeps the enabled-check in one place."""
    if _enabled:
        METRICS.counter(name).inc(n)


def record_replica_sync(n: int, dur_ms: float) -> None:
    """Replica adoption after a refresh/force-merge (one wall-time span
    covering all of an index's replica copies)."""
    METRICS.counter("indexing.replica.syncs").inc(int(n))
    METRICS.histogram("indexing.replica.sync_ms").record(dur_ms)


def record_bulk(items: int, nbytes: int, took_ms: float) -> None:
    METRICS.counter("indexing.bulk.requests").inc()
    METRICS.counter("indexing.bulk.items").inc(int(items))
    METRICS.counter("indexing.bulk.bytes").inc(int(nbytes))
    METRICS.histogram("indexing.bulk.took_ms").record(took_ms)


# ---------------- sizes ----------------

def segment_nbytes(seg) -> int:
    """Cheap host-side size of a segment's scoring payload (postings CSR
    arrays + impact planes) — the merge input/output byte accounting.
    Attribute sums only; never touches device residency."""
    total = 0
    for pb in getattr(seg, "postings", {}).values():
        for a in (pb.starts, pb.doc_ids, pb.tfs,
                  pb.pos_starts, pb.positions):
            if a is not None:
                total += int(a.nbytes)
        if pb.impact is not None:
            total += int(pb.impact.nbytes)
    return total


# ---------------- the `_nodes/stats` "indexing" block ----------------

def local_parts(registry=None) -> dict:
    """This node's `indexing.*` slice of the registry in wire form — the
    payload a member answers on the `/_internal` `indexing` op (counters
    and gauges as plain values, histograms as mergeable DDSketch wire)."""
    reg = registry if registry is not None else METRICS
    w = reg.to_wire()
    return {
        "counters": {k: v for k, v in w["counters"].items()
                     if k.startswith(PREFIX)},
        "gauges": {k: v for k, v in w["gauges"].items()
                   if k.startswith(PREFIX)},
        "histograms": {k: v for k, v in w["histograms"].items()
                       if k.startswith(PREFIX)},
    }


def merge_parts(parts_list: Sequence[dict]) -> dict:
    """Fold per-node parts into fleet parts: counters and gauges SUM
    (buffer docs/bytes and merge backlog are extensive quantities — the
    fleet buffer is the sum of node buffers), histograms merge bin-wise
    via `merge_sketches`. Commutative/associative like the PR 10
    federation ops, so member answer order never changes the result."""
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    hists: Dict[str, List[dict]] = {}
    for p in parts_list:
        if not isinstance(p, dict):
            continue
        for k, v in (p.get("counters") or {}).items():
            counters[k] = counters.get(k, 0) + v
        for k, v in (p.get("gauges") or {}).items():
            gauges[k] = gauges.get(k, 0) + v
        for k, w in (p.get("histograms") or {}).items():
            hists.setdefault(k, []).append(w)
    return {"counters": counters, "gauges": gauges,
            "histograms": {k: merge_sketches(ws)
                           for k, ws in sorted(hists.items())}}


_PER_INDEX_SUFFIX = ".refresh_to_visible_ms"
_BUILD_STAGES = ("pack", "spill", "chunk_merge", "quantize",
                 "device_promote")


def assemble_block(parts: dict, nodes: int = 1) -> dict:
    """The `_nodes/stats` `"indexing"` block from wire parts (local or
    fleet-merged — same assembly either way, so a 1-node block and the
    federated block differ only in the numbers). Mirrors the reference
    `_stats` layout: indexing / refresh / merge / flush / translog
    sub-blocks, plus the blocks the reference has no analog for (bulk
    accept, ingest pipelines, writer buffer, replica write-through,
    refresh-to-visible). Percentiles come from `sketch_snapshot` over
    the (possibly merged) sketch — never from averaging."""
    c = parts.get("counters") or {}
    g = parts.get("gauges") or {}
    h = parts.get("histograms") or {}

    def snap(name: str) -> dict:
        w = h.get(name)
        if w is None:
            return {"count": 0, "sum_ms": 0.0, "p50_ms": None,
                    "p95_ms": None, "p99_ms": None}
        return sketch_snapshot(w)

    per_index = {}
    for k in sorted(h):
        if k.startswith("indexing.index.") and k.endswith(_PER_INDEX_SUFFIX):
            idx = k[len("indexing.index."):-len(_PER_INDEX_SUFFIX)]
            per_index[idx] = {"refresh_to_visible_ms": sketch_snapshot(h[k])}

    build_detail = {f"{s}_ms": snap(f"indexing.refresh.build.{s}_ms")
                    for s in _BUILD_STAGES
                    if f"indexing.refresh.build.{s}_ms" in h}

    return {
        "nodes": int(nodes),
        "bulk": {
            "requests": int(c.get("indexing.bulk.requests", 0)),
            "items": int(c.get("indexing.bulk.items", 0)),
            "bytes": int(c.get("indexing.bulk.bytes", 0)),
            "item_failed": int(c.get("indexing.bulk.item_failed", 0)),
            "rejected": int(c.get("indexing.bulk.rejected", 0)),
            "took_ms": snap("indexing.bulk.took_ms"),
        },
        "indexing": {
            "index_total": int(c.get("indexing.docs.indexed", 0)),
            "delete_total": int(c.get("indexing.docs.deleted", 0)),
            "index_failed": int(c.get("indexing.docs.failed", 0)),
        },
        "ingest_pipeline": {
            "docs": int(c.get("indexing.pipeline.docs", 0)),
            "dropped": int(c.get("indexing.pipeline.dropped", 0)),
            "failed": int(c.get("indexing.pipeline.failed", 0)),
            "time_ms": snap("indexing.pipeline.time_ms"),
        },
        "buffer": {
            "docs": int(g.get("indexing.buffer.docs", 0)),
            "bytes": int(g.get("indexing.buffer.bytes", 0)),
        },
        "refresh": {
            "total": int(c.get("indexing.refresh.total", 0)),
            "stream_total": int(c.get("indexing.refresh.stream_total", 0)),
            "docs": int(c.get("indexing.refresh.docs", 0)),
            "stalls": int(c.get("indexing.refresh.stalls", 0)),
            "fanout_failed": int(c.get("indexing.refresh.fanout_failed", 0)),
            "time_ms": snap("indexing.refresh.time_ms"),
            "stages": {
                "collect_ms": snap("indexing.refresh.stage.collect_ms"),
                "build_ms": snap("indexing.refresh.stage.build_ms"),
                "publish_ms": snap("indexing.refresh.stage.publish_ms"),
                "merge_ms": snap("indexing.refresh.stage.merge_ms"),
            },
            "build_detail": build_detail,
            "refresh_to_visible_ms": snap("indexing.refresh_to_visible_ms"),
            "per_index": per_index,
        },
        "merge": {
            "total": int(c.get("indexing.merge.total", 0)),
            "input_segments": int(c.get("indexing.merge.input_segments", 0)),
            "input_docs": int(c.get("indexing.merge.input_docs", 0)),
            "output_docs": int(c.get("indexing.merge.output_docs", 0)),
            "input_bytes": int(c.get("indexing.merge.input_bytes", 0)),
            "output_bytes": int(c.get("indexing.merge.output_bytes", 0)),
            "backlog": int(g.get("indexing.merge.backlog", 0)),
            "time_ms": snap("indexing.merge.time_ms"),
            "reorder": {
                "total": int(c.get("indexing.merge.reorder_total", 0)),
                "time_ms": snap("indexing.merge.reorder_ms"),
            },
        },
        "flush": {
            "total": int(c.get("indexing.flush.total", 0)),
            "remote_failed": int(c.get("indexing.flush.remote_failed", 0)),
            "time_ms": snap("indexing.flush.time_ms"),
        },
        "translog": {
            "ops": int(c.get("indexing.translog.ops", 0)),
            "bytes": int(c.get("indexing.translog.bytes", 0)),
            "age_s": round(float(g.get("indexing.translog.age_s", 0.0)), 3),
        },
        "replica": {
            "syncs": int(c.get("indexing.replica.syncs", 0)),
            "write_through": int(c.get("indexing.replica.write_through", 0)),
            "failed": int(c.get("indexing.replica.failed", 0)),
            "sync_ms": snap("indexing.replica.sync_ms"),
            "fanout_ms": snap("indexing.replica.fanout_ms"),
        },
    }
