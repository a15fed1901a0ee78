"""The index engine: buffered writes, realtime get, refresh, flush/commit,
recovery. Analog of reference `index/engine/InternalEngine.java` +
`index/shard/IndexShard.java`.

Write path: parse → version/concurrency check → translog append → in-memory
buffer. `refresh()` turns the buffer into an immutable device-resident
Segment (the searchable unit). `flush()` persists segments + a commit point
and rolls the translog. Opening an engine on an existing path recovers from
the last commit point + translog replay (reference:
InternalEngine#recoverFromTranslog).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from ..obs import ingest_obs as _iobs
from ..utils.metrics import METRICS
from .mappings import Mappings, ParsedDocument
from .merge import TieredMergePolicy, merge_segments
from .segment import (Segment, build_segment, build_segment_streaming,
                      stream_eligible)
from .translog import Translog

# refresh buffers at or past this many docs take the streaming builder
# (chunked pack + disk spill-and-merge, index/segment.py
# StreamingSegmentBuilder) — the in-memory pack's transient Python token
# buffers dominate host memory well before the final CSR does. Output is
# bit-identical either way, so the threshold is purely a memory knob.
STREAM_REFRESH_MIN_DOCS = 1 << 16


def stream_refresh_min_docs() -> int:
    return int(os.environ.get("OPENSEARCH_TPU_STREAM_REFRESH_DOCS",
                              STREAM_REFRESH_MIN_DOCS))


class VersionConflictError(Exception):
    """Analog of reference VersionConflictEngineException (HTTP 409)."""


@dataclass
class DocLocation:
    seq_no: int
    in_buffer: bool
    segment: Optional[Segment] = None
    local_doc: int = -1
    buffer_idx: int = -1


class Engine:
    def __init__(self, mappings: Mappings, path: Optional[str] = None,
                 merge_policy: Optional[TieredMergePolicy] = None,
                 primary_term: int = 1):
        self.mappings = mappings
        self.path = path
        self.merge_policy = merge_policy or TieredMergePolicy()
        self.primary_term = primary_term
        self.index_name = ""       # set by IndexService; labels per-index obs
        self.segments: List[Segment] = []
        self.buffer: List[ParsedDocument] = []
        self.buffer_seq: List[int] = []
        # accept-time monotonic stamp per buffered doc (parallel to
        # `buffer`; survives tombstoning) — refresh publishes the
        # accept→searchable delta as `indexing.refresh_to_visible_ms`
        self.buffer_accepts: List[float] = []
        # what THIS engine contributed to the process buffer gauges —
        # refresh subtracts exactly this, so enable toggles mid-buffer
        # never skew the totals
        self._obs_buf_docs = 0
        self._obs_buf_bytes = 0
        # accepted docs not yet folded into the process gauges/counters
        # (amortized every ingest_obs.FLUSH_EVERY docs and at refresh)
        self._obs_pend_docs = 0
        self._buffer_ids: Dict[str, int] = {}
        self.seq_no = -1
        self._seg_counter = 0
        self.version_map: Dict[str, DocLocation] = {}
        self._tombstones: Dict[str, int] = {}
        self.translog: Optional[Translog] = None
        self.last_commit_gen = 0
        self.stats = {"index_ops": 0, "delete_ops": 0, "refreshes": 0,
                      "flushes": 0, "merges": 0}
        if path is not None:
            os.makedirs(path, exist_ok=True)
            self._recover()

    # ---------------- write path ----------------

    def _next_seq(self) -> int:
        self.seq_no += 1
        return self.seq_no

    def _check_concurrency(self, doc_id: str, if_seq_no: Optional[int],
                           if_primary_term: Optional[int], op: str) -> None:
        if if_seq_no is None and if_primary_term is None:
            return
        loc = self.version_map.get(doc_id)
        cur = loc.seq_no if loc else -1
        if if_seq_no is not None and cur != if_seq_no:
            raise VersionConflictError(
                f"[{doc_id}]: version conflict, required seqNo [{if_seq_no}], "
                f"current document has seqNo [{cur}] ({op})")
        if if_primary_term is not None and self.primary_term != if_primary_term:
            raise VersionConflictError(
                f"[{doc_id}]: version conflict on primary term ({op})")

    def index_doc(self, doc_id: str, source: dict, routing: Optional[str] = None,
                  if_seq_no: Optional[int] = None, if_primary_term: Optional[int] = None,
                  op_type: str = "index", translog_op: bool = True) -> dict:
        self._check_concurrency(doc_id, if_seq_no, if_primary_term, "index")
        existed = doc_id in self.version_map
        if op_type == "create" and existed:
            raise VersionConflictError(f"[{doc_id}]: document already exists")
        parsed = self.mappings.parse(doc_id, source, routing)
        seq = self._next_seq()
        if translog_op and self.translog is not None:
            self.translog.add_index(doc_id, source, routing, seq)
        self._delete_previous(doc_id)
        self._buffer_ids[doc_id] = len(self.buffer)
        self.buffer.append(parsed)
        self.buffer_seq.append(seq)
        self.buffer_accepts.append(time.monotonic())
        self.version_map[doc_id] = DocLocation(seq, in_buffer=True,
                                               buffer_idx=len(self.buffer) - 1)
        self._tombstones.pop(doc_id, None)
        self.stats["index_ops"] += 1
        if _iobs.enabled():
            # ONE int add — this runs under the index write lock on every
            # accepted doc; byte sizing and registry emission are
            # amortized via _obs_flush_pending (every FLUSH_EVERY docs +
            # at refresh). Anything heavier here is a measurable bulk
            # throughput hit at 32 submit threads.
            self._obs_pend_docs += 1
            if self._obs_pend_docs >= _iobs.FLUSH_EVERY:
                self._obs_flush_pending()
        return {"_id": doc_id, "_seq_no": seq, "_primary_term": self.primary_term,
                "result": "updated" if existed else "created"}

    def delete_doc(self, doc_id: str, if_seq_no: Optional[int] = None,
                   if_primary_term: Optional[int] = None, translog_op: bool = True) -> dict:
        self._check_concurrency(doc_id, if_seq_no, if_primary_term, "delete")
        found = doc_id in self.version_map
        seq = self._next_seq()
        if translog_op and self.translog is not None:
            self.translog.add_delete(doc_id, seq)
        if found:
            self._delete_previous(doc_id)
            del self.version_map[doc_id]
            self._tombstones[doc_id] = seq
        self.stats["delete_ops"] += 1
        if _iobs.enabled():
            METRICS.counter("indexing.docs.deleted").inc()
        return {"_id": doc_id, "_seq_no": seq, "_primary_term": self.primary_term,
                "result": "deleted" if found else "not_found"}

    def _delete_previous(self, doc_id: str) -> None:
        loc = self.version_map.get(doc_id)
        if loc is None:
            return
        if loc.in_buffer:
            idx = self._buffer_ids.pop(doc_id, None)
            if idx is not None:
                # tombstone the buffered doc (compacted away at refresh)
                self.buffer[idx] = None
        else:
            loc.segment.delete_doc(loc.local_doc)

    # ---------------- realtime get ----------------

    def get(self, doc_id: str) -> Optional[dict]:
        """Realtime get through the version map (reference: InternalEngine#get
        refreshes-on-demand; our buffer is directly readable so no refresh)."""
        loc = self.version_map.get(doc_id)
        if loc is None:
            return None
        if loc.in_buffer:
            parsed = self.buffer[loc.buffer_idx]
            return {"_id": doc_id, "_source": parsed.source, "_seq_no": loc.seq_no,
                    "_primary_term": self.primary_term, "found": True}
        return {"_id": doc_id, "_source": loc.segment.sources[loc.local_doc],
                "_seq_no": loc.seq_no, "_primary_term": self.primary_term, "found": True}

    # ---------------- refresh / merge / flush ----------------

    @property
    def num_docs(self) -> int:
        return sum(s.live_count for s in self.segments) + \
            sum(1 for d in self.buffer if d is not None)

    def refresh(self) -> bool:
        # Stage boundaries t0..t4 partition the refresh wall time EXACTLY
        # (stage_i = t_{i+1} - t_i, so collect+build+publish+merge equals
        # the total by construction — tests/test_ingest_obs.py pins it).
        # Stamps are unconditional (4 perf_counter reads per refresh);
        # everything else is gated on the ingest-obs flag.
        t0 = time.perf_counter()
        obs_on = _iobs.enabled()
        self._obs_flush_pending()
        live_docs = [(d, s, a) for d, s, a in
                     zip(self.buffer, self.buffer_seq, self.buffer_accepts)
                     if d is not None]
        self.buffer = []
        self.buffer_seq = []
        self.buffer_accepts = []
        self._buffer_ids = {}
        if self._obs_buf_docs or self._obs_buf_bytes:
            _iobs.buffer_delta(-self._obs_buf_docs, -self._obs_buf_bytes)
            self._obs_buf_docs = 0
            self._obs_buf_bytes = 0
        if not live_docs:
            return False
        docs = [d for d, _, _ in live_docs]
        seqs = [s for _, s, _ in live_docs]
        accepts = [a for _, _, a in live_docs]
        name = f"_{self._seg_counter}"
        self._seg_counter += 1
        t1 = time.perf_counter()
        streamed = False
        with _iobs.stage_scope() as build_detail:
            if len(docs) >= stream_refresh_min_docs() and stream_eligible(docs):
                seg = build_segment_streaming(name, docs, self.mappings,
                                              seq_nos=seqs,
                                              spill_dir=(os.path.join(
                                                  self.path, "_stream_spill")
                                                  if self.path else None))
                self.stats["stream_refreshes"] = \
                    self.stats.get("stream_refreshes", 0) + 1
                streamed = True
            else:
                seg = build_segment(name, docs, self.mappings, seq_nos=seqs)
        t2 = time.perf_counter()
        self.segments.append(seg)
        for local, d in enumerate(docs):
            self.version_map[d.doc_id] = DocLocation(
                seqs[local], in_buffer=False, segment=seg, local_doc=local)
        self.stats["refreshes"] += 1
        t3 = time.perf_counter()
        # the docs became searchable at publish (t3): record the honest
        # accept→visible delta BEFORE the piggybacked merge work
        if obs_on:
            _iobs.record_refresh_to_visible(self.index_name, accepts,
                                            time.monotonic())
        self.maybe_merge()
        t4 = time.perf_counter()
        if obs_on:
            _iobs.record_refresh(self.index_name, len(docs), streamed,
                                 (t0, t1, t2, t3, t4), build_detail,
                                 self.merge_backlog())
        return True

    def maybe_merge(self) -> None:
        for group in self.merge_policy.find_merges(self.segments):
            if len(group) < 2 and not any(s.live_count < s.ndocs for s in group):
                continue
            self.force_merge_group(group)

    def _obs_flush_pending(self) -> None:
        """Fold the accepted docs since the last fold into the process
        buffer gauges and the indexed counter (amortization contract:
        ingest_obs.FLUSH_EVERY). Bytes are a sampled structural
        estimate: size at most BYTES_SAMPLE docs from the buffer tail
        (the ones this fold covers) and scale to the fold — the gauge
        is an estimate by contract, and sizing every doc is a measured
        bulk-throughput hit. Must run before the buffer is cleared."""
        n = self._obs_pend_docs
        if not n:
            return
        tail = self.buffer[-n:]
        samples = [p for p in tail[::max(1, n // _iobs.BYTES_SAMPLE)]
                   if p is not None][:_iobs.BYTES_SAMPLE]
        est = (int(sum(_iobs.doc_bytes(p.source) for p in samples)
                   / len(samples) * n) if samples else 0)
        self._obs_buf_docs += n
        self._obs_buf_bytes += est
        self._obs_pend_docs = 0
        _iobs.buffer_delta(n, est)
        METRICS.counter("indexing.docs.indexed").inc(n)

    def merge_backlog(self) -> int:
        """Merge groups the policy would run right now — this engine's
        slice of the `indexing.merge.backlog` write-pressure gauge (0
        right after `maybe_merge` unless max_merged_docs defers work)."""
        return len([g for g in self.merge_policy.find_merges(self.segments)
                    if len(g) >= 2
                    or any(s.live_count < s.ndocs for s in g)])

    def buffer_stats(self) -> dict:
        """Live writer-buffer shape (docs pending refresh + tracked byte
        estimate) for `_stats` / `_cat/indices`."""
        return {"docs": sum(1 for d in self.buffer if d is not None),
                "bytes": self._obs_buf_bytes}

    def force_merge_group(self, group: List[Segment]) -> Segment:
        name = f"_m{self._seg_counter}"
        self._seg_counter += 1
        merged = merge_segments(name, group)
        group_set = set(id(s) for s in group)
        self.segments = [s for s in self.segments if id(s) not in group_set]
        self.segments.append(merged)
        for local, doc_id in enumerate(merged.ids):
            loc = self.version_map.get(doc_id)
            if loc is not None and not loc.in_buffer:
                self.version_map[doc_id] = DocLocation(
                    int(merged.seq_nos[local]), in_buffer=False,
                    segment=merged, local_doc=local)
        self.stats["merges"] += 1
        return merged

    def force_merge(self, max_num_segments: int = 1) -> None:
        if len(self.segments) > max_num_segments:
            self.force_merge_group(list(self.segments))
            return
        # a lone codec-v2 segment still takes the merge-time BP reorder
        # pass (index/reorder.py): forcemerge is the "optimize layout"
        # call, and whether the corpus arrived in one refresh or ten must
        # not decide whether the pass ran. Gated on the pass actually
        # being applicable so small/v1/already-reordered segments keep
        # the historical no-op.
        from . import reorder
        from .segment import CODEC_V2
        if (len(self.segments) == 1 and reorder.enabled()
                and getattr(self.segments[0], "codec_version", 1)
                >= CODEC_V2
                and not self.segments[0].__dict__.get("_reordered")
                and self.segments[0].ndocs >= reorder.min_docs()):
            self.force_merge_group(list(self.segments))

    def flush(self) -> None:
        """Durable commit: segments to disk + commit point, translog rolled
        (reference: InternalEngine#flush -> Lucene commit + translog trim)."""
        t0 = time.perf_counter()
        self.refresh()
        if self.path is None:
            return
        seg_dir = os.path.join(self.path, "segments")
        committed = []
        for seg in self.segments:
            d = os.path.join(seg_dir, seg.name)
            if not os.path.exists(os.path.join(d, "meta.json")):
                seg.save(d)
            else:
                # persist up-to-date live masks for previously saved segments
                import numpy as np
                seg.save(d)
            committed.append(seg.name)
        # translog age at commit = how stale durability was just before
        # this flush (measured BEFORE rollover resets the generation)
        tl_age = self.translog.age_s() if self.translog else 0.0
        gen = self.translog.rollover() if self.translog else 0
        commit = {"segments": committed, "seq_no": self.seq_no,
                  "translog_gen": gen, "primary_term": self.primary_term,
                  "ts": time.time()}
        tmp = os.path.join(self.path, "commit.json.tmp")
        with open(tmp, "w") as fh:
            json.dump(commit, fh)
        os.replace(tmp, os.path.join(self.path, "commit.json"))
        if self.translog:
            self.translog.prune_below(gen)
        self.last_commit_gen = gen
        self.stats["flushes"] += 1
        if _iobs.enabled():
            _iobs.record_flush((time.perf_counter() - t0) * 1000.0, tl_age)

    # ---------------- recovery ----------------

    def _recover(self) -> None:
        commit_path = os.path.join(self.path, "commit.json")
        translog_dir = os.path.join(self.path, "translog")
        gen = 0
        if os.path.exists(commit_path):
            with open(commit_path) as fh:
                commit = json.load(fh)
            for name in commit["segments"]:
                seg = Segment.load(os.path.join(self.path, "segments", name))
                self.segments.append(seg)
                num = int(name.lstrip("_m").lstrip("_") or 0)
                self._seg_counter = max(self._seg_counter, num + 1)
                for local, doc_id in enumerate(seg.ids):
                    if seg.live[local]:
                        self.version_map[doc_id] = DocLocation(
                            int(seg.seq_nos[local]), in_buffer=False,
                            segment=seg, local_doc=local)
            self.seq_no = commit["seq_no"]
            gen = commit["translog_gen"]
            self.primary_term = commit.get("primary_term", 1)
        self.translog = Translog(translog_dir, generation=gen)
        replayed = 0
        for rec in self.translog.replay_from(gen):
            if rec["seq_no"] <= self.seq_no and os.path.exists(commit_path):
                continue
            if rec["op"] == "index":
                self.index_doc(rec["_id"], rec["_source"], rec.get("routing"),
                               translog_op=False)
            else:
                self.delete_doc(rec["_id"], translog_op=False)
            replayed += 1
        if replayed:
            self.refresh()

    # ---------------- index-wide stats for scoring ----------------

    def codec_mix(self) -> Dict[int, int]:
        """Live segments per codec version — the serving tier can carry a
        mixed v1/v2 set indefinitely (v1 loads untouched; refresh/merge
        emit the process default). Surfaced by scripts/hbm_report.py."""
        mix: Dict[int, int] = {}
        for s in self.segments:
            v = int(getattr(s, "codec_version", 1))
            mix[v] = mix.get(v, 0) + 1
        return mix

    def field_stats(self, field: str):
        """Index-wide (doc_count, sum_dl, total_docs) for BM25 avgdl/idf —
        the analog of Lucene CollectionStatistics aggregated across leaves."""
        doc_count = 0
        sum_dl = 0
        for s in self.segments:
            st = s.text_stats.get(field)
            if st:
                doc_count += st.doc_count
                sum_dl += st.sum_dl
        return doc_count, sum_dl

    def doc_freq(self, field: str, term: str) -> int:
        return sum(s.postings[field].doc_freq(term)
                   for s in self.segments if field in s.postings)

    def close(self) -> None:
        if self.translog:
            self.translog.close()
