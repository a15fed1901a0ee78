"""Per-query device cost accounting: predicted vs. actual bytes moved.

The HBM ledger (`obs/hbm_ledger.py`) answers "what is resident"; this
module answers "what does one query MOVE". Two curves drive device sparse
retrieval engineering (GPUSparse, PAPERS.md arxiv 2606.26441): resident
footprint vs. bytes gathered per query — and ROADMAP item 1
(impact-quantized postings) claims to shrink the second. This module
commits the baseline that claim will be measured against.

Model (documented in docs/OBSERVABILITY.md):

- **Predicted, at plan time, from CSR block stats only.** For each scoring
  term group the query touches in a segment, every term row contributes
  its true posting count `df`; a codec-v1 posting slot is 8 bytes
  (doc_id i32 + tf/packed-tfdl f32/i32), a codec-v2 eager slot is
  `4 + bits/8` bytes (doc_id i32 + u8/u16 quantized impact — the
  executor's `_cost_predicted` consults the segment codec per field).
  `predicted_bytes_gathered = Σ df × slot`, `predicted_scatter_adds =
  Σ df`, `predicted_topk_work = window` per planned segment.
  A `nested` clause adds what its join reads whatever matches: 4 bytes
  (the parent map) and one scatter update a scatter for every child row
  of the segment's block, and its child clause is priced against the
  child space's postings.
- **Actual, from launched program shapes.** The programs gather PADDED
  shapes: the XLA path flattens a term group into a pow2 `bucket`
  (`ops.pick_bucket`), so it moves `bucket × 8` bytes and scatter-adds
  `bucket` slots; the codec-v2 impact pass (search/impactpath.py, path
  "impact") moves `2 × B_pad × 128 × (4 + bits/8)` bytes over its
  block-pruned windows (one row of 128 slots a kept block slot, read as
  the two 128-posting plane rows its window lies in); the fastpath
  kernel DMAs per-term lane-aligned windows
  (`nrows × LANES` slots of 8 bytes) and extracts `K` top-k lanes per
  kernel row. The predicted/actual gap is therefore exactly the padding +
  alignment tax.

An accumulator rides a contextvar for the duration of one
`executor.search_shards` call (the host shard loop + fastpath ladder; the
mesh SPMD path and cross-request coalesced batches execute on other
threads and are attributed to their own launch counters instead). At
finish it records DDSketch histograms (`cost.bytes_per_query`,
`cost.predicted_bytes_per_query`, `cost.predicted_vs_actual_pct`) served
by `_nodes/stats` and `/_metrics`, and the snapshot surfaces as the
`cost` block of a `profile` response and the `explain=device_plan` view.

`OPENSEARCH_TPU_COST=0` disables accounting entirely (a `profile`
response then carries no `cost` block).
"""

from __future__ import annotations

import contextvars
import os
import threading
from typing import List, Optional, Tuple

from ..utils.metrics import METRICS

__all__ = ["QueryCost", "current", "start", "finish", "enabled",
           "POSTING_SLOT_BYTES", "NESTED_CHILD_BYTES", "nested_join_scatters",
           "spec_gather_shape"]

# bytes moved per posting slot: doc_id i32 + (tf f32 | packed tf·dl i32)
POSTING_SLOT_BYTES = 8

# bytes the block join reads a child row: its parent (i32)
NESTED_CHILD_BYTES = 4


def nested_join_scatters(score_mode: str) -> int:
    """Scatters the to-parent join of `score_mode` takes over the child
    space: the count, and the sum or extreme where children score."""
    return 1 if score_mode == "none" else 2


_current: contextvars.ContextVar = contextvars.ContextVar(
    "opensearch_tpu_query_cost", default=None)


def enabled() -> bool:
    return os.environ.get("OPENSEARCH_TPU_COST", "") not in (
        "0", "false", "no")


class QueryCost:
    """Accumulates one search's predicted and actual device work.

    Thread-safe: the fastpath ladder's escalation rungs and pool-executed
    segment work may note from worker threads carrying the contextvar."""

    __slots__ = ("detail", "predicted_bytes", "predicted_scatter",
                 "predicted_topk", "actual_bytes", "actual_scatter",
                 "actual_topk", "launches", "segments", "_lock")

    def __init__(self, detail: bool = False) -> None:
        self.detail = detail
        self.predicted_bytes = 0
        self.predicted_scatter = 0
        self.predicted_topk = 0
        self.actual_bytes = 0
        self.actual_scatter = 0
        self.actual_topk = 0
        self.launches = 0
        # per-segment plan entries (explain=device_plan only)
        self.segments: List[dict] = []
        self._lock = threading.Lock()

    def note_predicted(self, bytes_: int, scatter: int, topk: int,
                       segment=None) -> None:
        with self._lock:
            self.predicted_bytes += int(bytes_)
            self.predicted_scatter += int(scatter)
            self.predicted_topk += int(topk)
            if self.detail and segment is not None:
                self.segments.append(  # oslint: disable=OSL602 -- per-request accumulator: dies at finish(), bounded by the request's own plan size, never workload cardinality
                    {"segment": getattr(segment, "name", str(segment)),
                     "predicted_bytes_gathered": int(bytes_),
                     "predicted_scatter_adds": int(scatter),
                     "predicted_topk_work": int(topk)})

    def note_actual(self, bytes_: int, scatter: int, topk: int,
                    launches: int = 1, path: str = "",
                    segment=None) -> None:
        with self._lock:
            self.actual_bytes += int(bytes_)
            self.actual_scatter += int(scatter)
            self.actual_topk += int(topk)
            self.launches += int(launches)
            if self.detail:
                self.segments.append(  # oslint: disable=OSL602 -- per-request accumulator: dies at finish(), bounded by the request's own plan size, never workload cardinality
                    {"segment": (getattr(segment, "name", str(segment))
                                 if segment is not None else None),
                     "path": path,
                     "actual_bytes_gathered": int(bytes_),
                     "actual_scatter_adds": int(scatter),
                     "actual_topk_work": int(topk),
                     "launches": int(launches)})

    @property
    def active(self) -> bool:
        return bool(self.launches or self.predicted_bytes
                    or self.actual_bytes)

    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "predicted_bytes_gathered": self.predicted_bytes,
                "predicted_scatter_adds": self.predicted_scatter,
                "predicted_topk_work": self.predicted_topk,
                "actual_bytes_gathered": self.actual_bytes,
                "actual_scatter_adds": self.actual_scatter,
                "actual_topk_work": self.actual_topk,
                "launches": self.launches,
            }
            if self.actual_bytes and self.predicted_bytes:
                out["predicted_vs_actual_pct"] = round(
                    100.0 * self.predicted_bytes / self.actual_bytes, 2)
            return out


def current() -> Optional[QueryCost]:
    return _current.get()


def start(detail: bool = False) -> tuple:
    """Install a fresh accumulator; returns (accumulator, token) for the
    paired `finish`."""
    qc = QueryCost(detail=detail)
    return qc, _current.set(qc)


def finish(token, record: bool = True) -> None:
    """Uninstall and (when the query did device work) record the
    per-query histograms."""
    qc = _current.get()
    _current.reset(token)
    if qc is None or not record or not qc.active:
        return
    if METRICS.enabled:
        # DDSketch histograms: values are BYTES (the registry's log bins
        # are value-agnostic; the *_ms key names in snapshots read as
        # raw-unit values for these series)
        if qc.actual_bytes:
            METRICS.histogram("cost.bytes_per_query").record(
                float(qc.actual_bytes))
        if qc.predicted_bytes:
            METRICS.histogram("cost.predicted_bytes_per_query").record(
                float(qc.predicted_bytes))
        if qc.actual_bytes and qc.predicted_bytes:
            METRICS.histogram("cost.predicted_vs_actual_pct").record(
                100.0 * qc.predicted_bytes / qc.actual_bytes)


def bytes_per_query_stamp() -> dict:
    """count/p50/p95 of the predicted and actual bytes-gathered
    histograms plus the reconciliation percentiles, for
    scripts/hbm_report.py. The DDSketch snapshot's `*_ms` keys carry raw
    BYTE values for these series (the registry's log bins are
    unit-agnostic)."""
    hists = METRICS.snapshot()["histograms"]

    def _pct(name: str) -> dict:
        h = hists.get(name) or {}
        return {"count": h.get("count", 0), "p50": h.get("p50_ms"),
                "p95": h.get("p95_ms")}

    return {"actual": _pct("cost.bytes_per_query"),
            "predicted": _pct("cost.predicted_bytes_per_query"),
            "predicted_vs_actual_pct": _pct("cost.predicted_vs_actual_pct")}


# ---------------------------------------------------------------------
# launched-shape walkers
# ---------------------------------------------------------------------

# (spec kind, index of the pow2 gather bucket in the spec tuple): the
# compiler spec tuples whose programs flatten postings through
# `ops.gather_postings` — the launched gather width is the bucket
_BUCKET_SPECS = {"terms": 4, "xterms": 4, "sparse_dot": 4,
                 "rank_feature_post": 3}


def spec_gather_shape(spec) -> Tuple[int, int]:
    """-> (bytes_gathered, scatter_adds) of one prepared query spec tree,
    from the pow2 buckets its launched program will actually move.
    Aggregation specs reuse some kind names ("terms", "range") with
    string prefixes in slot 1 — query specs carry an int nid there, which
    is the discriminator."""
    bytes_ = 0
    slots = 0
    stack = [spec]
    while stack:
        node = stack.pop()
        if not isinstance(node, (tuple, list)):
            continue
        if node and isinstance(node[0], str) and len(node) > 1 \
                and isinstance(node[1], int):
            kind = node[0]
            bi = _BUCKET_SPECS.get(kind)
            if bi is not None and len(node) > bi \
                    and isinstance(node[bi], int):
                bytes_ += node[bi] * POSTING_SLOT_BYTES
                slots += node[bi]
            elif kind == "phrase" and len(node) > 4 \
                    and isinstance(node[4], tuple):
                # (doc i32, pos i32) a slot: the anchor's window, and a
                # pair a search round for each of its slots and other terms
                bucket, depth = node[4]
                read = bucket * (1 + (node[3] - 1) * depth)
                bytes_ += read * POSTING_SLOT_BYTES
                slots += read
            elif kind == "nested" and len(node) > 5:
                # the block join: the parent map (i32) of every padded
                # child slot, and an update a slot of each of its scatters
                # (`nested_join_scatters`); the child clause's own gathers
                # are the nodes below
                bytes_ += node[5] * NESTED_CHILD_BYTES
                slots += node[5] * nested_join_scatters(node[3])
        stack.extend(node)
    return bytes_, slots
