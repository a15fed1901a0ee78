"""RestClient: the user-facing API façade mirroring the OpenSearch REST
surface (reference `rest/action/*`, `action/admin/*`, and the opensearch-py
client method names). Dict-in / dict-out with the same JSON shapes, HTTP-less.

Doc APIs route through the cluster's write index + murmur3 shard routing;
search fans out over shard searchers and reduces like the coordinator node.
"""

from __future__ import annotations

import copy
import json
import time
import uuid
from typing import Any, Dict, List, Optional

from ..cluster.node import Node
from ..cluster.admin import IndexClosedError
from ..cluster.state import IndexNotFoundError
from ..index.engine import VersionConflictError
from ..ingest.pipeline import DropDocument
from ..search.executor import ShardSearcher, explain_doc, search_shards
from ..search import compiler as C, plan as PL
from ..search import fastpath as _fastpath
from ..search import query_dsl as dsl
from ..search.pipeline import SearchPipelineException
from ..obs import ingest_obs as _iobs
from ..utils.breaker import CircuitBreakingException
from ..utils.tasks import TaskCancelledException
from ..utils.wlm import PressureRejectedException


class ApiError(Exception):
    def __init__(self, status: int, err_type: str, reason: str,
                 headers: Optional[dict] = None):
        super().__init__(reason)
        self.status = status
        self.err_type = err_type
        self.reason = reason
        # extra HTTP response headers (e.g. Retry-After on 429s); the
        # wire layer sends them, dict-level callers can read them
        self.headers = dict(headers or {})

    def body(self) -> dict:
        return {"error": {"type": self.err_type, "reason": self.reason},
                "status": self.status}


def _rejected_429(e) -> ApiError:
    """PressureRejectedException -> 429, carrying the rejecting layer's
    Retry-After hint (scheduler queue drain estimate / remediation TTL)
    as an HTTP header — delay-seconds form, ceil'd, min 1."""
    import math
    headers = {}
    ra = getattr(e, "retry_after_s", None)
    if ra is not None and ra > 0:
        headers["Retry-After"] = str(max(int(math.ceil(ra)), 1))
    return ApiError(429, "rejected_execution_exception", str(e),
                    headers=headers)


def _run_update_script_or_400(script_body, src: dict, meta: dict):
    """Deep-copy `src`, run the update script, map ScriptError to 400.
    The deep copy matters: engine.get() hands back the live stored _source,
    and a script that mutates nested state then sets ctx.op='none' must not
    corrupt the segment in place."""
    import copy

    from ..script import ScriptError, run_update_script
    from ..search.query_dsl import parse_script_spec
    src_str, prm = parse_script_spec(script_body)
    try:
        return run_update_script(src_str, prm, copy.deepcopy(src), meta)
    except ScriptError as e:
        raise ApiError(400, "illegal_argument_exception",
                       f"failed to execute script: {e}")


def _parse_keepalive_s(v, default: float = 60.0) -> float:
    """'1m' / '30s' / '500ms' -> seconds (scroll/PIT keep-alives); invalid
    values are client errors (HTTP 400)."""
    if v is None:
        return default
    if isinstance(v, (int, float)):
        return float(v)
    sv = str(v).strip()
    try:
        for suf, mult in (("micros", 1e-6), ("nanos", 1e-9), ("ms", 0.001),
                          ("s", 1.0), ("m", 60.0), ("h", 3600.0),
                          ("d", 86400.0)):
            if sv.endswith(suf):
                return float(sv[: -len(suf)]) * mult
        return float(sv)
    except ValueError:
        raise ApiError(400, "illegal_argument_exception",
                       f"failed to parse time value [{v}]")


class RestClient:
    def __init__(self, node: Optional[Node] = None,
                 data_path: Optional[str] = None,
                 remote_root: Optional[str] = None):
        self.node = node or Node(data_path=data_path, remote_root=remote_root)
        self.indices = IndicesClient(self)
        self.ingest = IngestClient(self)
        self.snapshot = SnapshotClient(self)
        self.cluster = ClusterClient(self)
        self.cat = CatClient(self)
        self._scrolls: Dict[str, dict] = {}
        self._pits: Dict[str, dict] = {}
        self._stored_scripts: Dict[str, Any] = {}

    # ---------------- document APIs ----------------

    def _svc_for_write(self, index: str, auto_create: bool = True):
        try:
            return self.node.index_service_for_write(index, auto_create)
        except IndexClosedError as e:
            raise ApiError(400, "index_closed_exception", str(e))

    def _check_write_block(self, svc) -> None:
        """index.blocks.write / read_only (set by hand, PUT _settings, or
        the ILM read_only action) reject writes like the reference
        ClusterBlockException."""
        blocks = svc.meta.settings.get("index", {}).get("blocks", {})
        if blocks.get("write") or blocks.get("read_only"):
            raise ApiError(403, "cluster_block_exception",
                           f"index [{svc.meta.name}] blocked by: "
                           f"[FORBIDDEN/8/index write (api)]")

    def index(self, index: str, body: dict, id: Optional[str] = None,
              routing: Optional[str] = None, refresh: bool = False,
              op_type: str = "index", pipeline: Optional[str] = None,
              if_seq_no: Optional[int] = None,
              if_primary_term: Optional[int] = None,
              _no_pipeline: bool = False) -> dict:
        if index in self.node.metadata.data_streams:
            from ..cluster import datastream as dstream
            _map_ds_errors(dstream.check_write, self.node, index, op_type,
                           body)
        svc = self._svc_for_write(index)
        self._check_write_block(svc)
        # update()'s internal rewrite (RMW under the index write lock)
        # must land in the SAME index: no pipelines, no _index redirects
        # (this also matches the reference, where the update's final index
        # op does not re-run ingest pipelines on the merged source)
        if not _no_pipeline:
            pipeline = pipeline or svc.meta.settings.get(
                "index", {}).get("default_pipeline")
        if pipeline and not _no_pipeline:
            try:
                body = self.node.ingest.run(pipeline, dict(body))
            except DropDocument:
                body = None
            if body is None:
                return {"_index": index, "_id": id or "", "result": "noop"}
            # date_index_name (and any processor that rewrites _index)
            # redirects the doc — resolve the new target before routing,
            # and re-authorize it against the ambient request subject
            # (the transport authorized only the ORIGINAL request index)
            new_index = body.pop("_index", None)
            if new_index and new_index != index:
                from ..security.context import authorize_index_if_active
                from ..security.identity import AuthorizationError
                try:
                    authorize_index_if_active(new_index, "write")
                except AuthorizationError as e:
                    # ApiError so bulk reports it PER ITEM (committed
                    # siblings stay committed, like the reference's
                    # per-item security failures)
                    raise ApiError(403, "security_exception", str(e))
                index = new_index
                svc = self._svc_for_write(index)
                self._check_write_block(svc)
        doc_id = id if id is not None else uuid.uuid4().hex[:20]
        t0 = time.monotonic()
        # per-index write serialization at the engine boundary, AFTER
        # alias/data-stream/pipeline-_index resolution picked the final
        # svc — so every transport is covered and two request names that
        # resolve to the same engine share one lock
        with svc.write_lock:
            # re-check under the lock: a concurrent index delete may have
            # popped this svc between resolution and acquisition — fail
            # like the doc write arrived after the delete, never write
            # into an orphaned engine
            if self.node.indices.get(svc.meta.name) is not svc:
                raise IndexNotFoundError(
                    f"no such index [{svc.meta.name}]")
            try:
                res = svc.route(doc_id, routing).index_doc(
                    doc_id, body, routing, if_seq_no, if_primary_term,
                    op_type)
            except VersionConflictError as e:
                raise ApiError(409, "version_conflict_engine_exception",
                               str(e))
            except ValueError as e:
                # document parse failures (bad geo shapes/vectors/strict
                # dynamic mapping) are client errors, reference
                # mapper_parsing_exception
                raise ApiError(400, "mapper_parsing_exception", str(e))
            svc.generation += 1
            if refresh:
                svc.refresh()
        took = time.monotonic() - t0
        self.node.op_counters["index_total"] += 1
        self.node.op_counters["index_time_ms"] += took * 1000.0
        svc.index_slowlog.maybe_log(took, {"_id": doc_id})
        res["_index"] = svc.meta.name
        res["_shards"] = {"total": 1, "successful": 1, "failed": 0}
        return res

    def create(self, index: str, id: str, body: dict, **kw) -> dict:
        return self.index(index, body, id=id, op_type="create", **kw)

    def get(self, index: str, id: str, routing: Optional[str] = None) -> dict:
        svc = self.node.get_index(self.node.metadata.write_index(index))
        self.node.op_counters["get_total"] += 1
        res = svc.route(id, routing).get(id)
        if res is None:
            raise ApiError(404, "document_missing_exception",
                           f"[{id}]: document missing")
        res["_index"] = svc.meta.name
        return res

    def exists(self, index: str, id: str, routing: Optional[str] = None) -> bool:
        try:
            self.get(index, id, routing)
            return True
        except (ApiError, IndexNotFoundError):
            return False

    def mget(self, body: dict, index: Optional[str] = None) -> dict:
        docs = []
        for spec in body.get("docs", []):
            idx = spec.get("_index", index)
            try:
                docs.append(self.get(idx, spec["_id"], spec.get("routing")))
            except (ApiError, IndexNotFoundError):
                docs.append({"_index": idx, "_id": spec["_id"], "found": False})
        return {"docs": docs}

    def delete(self, index: str, id: str, routing: Optional[str] = None,
               refresh: bool = False, if_seq_no: Optional[int] = None,
               if_primary_term: Optional[int] = None) -> dict:
        svc = self.node.get_index(self.node.metadata.write_index(index))
        if svc.meta.state == "close":
            raise ApiError(400, "index_closed_exception",
                           f"closed index [{svc.meta.name}]")
        self._check_write_block(svc)
        with svc.write_lock:
            if self.node.indices.get(svc.meta.name) is not svc:
                raise IndexNotFoundError(
                    f"no such index [{svc.meta.name}]")
            try:
                res = svc.route(id, routing).delete_doc(id, if_seq_no,
                                                        if_primary_term)
            except VersionConflictError as e:
                raise ApiError(409, "version_conflict_engine_exception",
                               str(e))
            svc.generation += 1
            if refresh:
                svc.refresh()
        res["_index"] = svc.meta.name
        if res["result"] == "not_found":
            raise ApiError(404, "document_missing_exception", f"[{id}]: not found")
        return res

    def update(self, index: str, id: str, body: dict, routing: Optional[str] = None,
               refresh: bool = False, **kw) -> dict:
        """Partial-doc update / upsert (reference UpdateHelper)."""
        svc = self._svc_for_write(index)
        self._check_write_block(svc)
        # hold the index's write lock across the WHOLE read-modify-write
        # (reentrant: the nested self.index() re-acquires) so concurrent
        # updates of one doc can't lose each other's changes
        with svc.write_lock:
            return self._update_locked(svc, index, id, body, routing,
                                       refresh, **kw)

    def _update_locked(self, svc, index: str, id: str, body: dict,
                       routing: Optional[str], refresh: bool, **kw) -> dict:
        eng = svc.route(id, routing)
        current = eng.get(id)
        if current is None:
            if body.get("doc_as_upsert") and "doc" in body:
                return self.index(index, body["doc"], id=id, routing=routing,
                                  refresh=refresh, _no_pipeline=True)
            if "upsert" in body:
                upsert_src = dict(body["upsert"])
                if body.get("scripted_upsert") and "script" in body:
                    upsert_src, op = _run_update_script_or_400(
                        body["script"], upsert_src,
                        {"_index": svc.meta.name, "_id": id, "op": "create"})
                    if op in ("none", "delete"):
                        return {"_index": svc.meta.name, "_id": id, "result": "noop"}
                return self.index(index, upsert_src, id=id, routing=routing,
                                  refresh=refresh, _no_pipeline=True)
            raise ApiError(404, "document_missing_exception", f"[{id}]: document missing")
        src = dict(current["_source"])
        if "doc" in body:
            merged = _deep_merge(src, body["doc"])
            if body.get("detect_noop", True) and merged == src:
                return {"_index": svc.meta.name, "_id": id, "result": "noop"}
            return self.index(index, merged, id=id, routing=routing,
                              refresh=refresh, _no_pipeline=True)
        if "script" in body:
            meta = {"_index": svc.meta.name, "_id": id,
                    "_version": current.get("_version", 1),
                    "_routing": routing}
            new_src, op = _run_update_script_or_400(body["script"], src, meta)
            if op == "none":
                return {"_index": svc.meta.name, "_id": id, "result": "noop"}
            if op == "delete":
                return self.delete(index, id, routing=routing, refresh=refresh)
            return self.index(index, new_src, id=id, routing=routing,
                              refresh=refresh, _no_pipeline=True)
        raise ApiError(400, "action_request_validation_exception",
                       "update requires doc, upsert or script")

    def bulk(self, body, index: Optional[str] = None, refresh: bool = False) -> dict:
        """Bulk API. Accepts NDJSON string or a list of alternating
        action/source dicts (reference RestBulkAction)."""
        t0 = time.perf_counter()
        if isinstance(body, str):
            lines = [json.loads(ln) for ln in body.splitlines() if ln.strip()]
        else:
            lines = list(body)
        # indexing pressure admission (reference IndexingPressure): budget
        # in-flight bulk bytes, reject with 429 when saturated
        est_bytes = sum(len(repr(ln)) for ln in lines)
        try:
            self.node.wlm.indexing.acquire(est_bytes)
        except PressureRejectedException as e:
            _iobs.count("indexing.bulk.rejected")
            raise ApiError(429, "rejected_execution_exception", str(e))
        try:
            out = self._bulk_inner(lines, index, refresh)
            if _iobs.enabled():
                _iobs.record_bulk(len(out["items"]), est_bytes,
                                  (time.perf_counter() - t0) * 1000.0)
            return out
        finally:
            self.node.wlm.indexing.release(est_bytes)

    def _bulk_inner(self, lines, index: Optional[str], refresh: bool) -> dict:
        items = []
        errors = False
        touched = set()
        i = 0
        while i < len(lines):
            action_line = lines[i]
            ((action, meta),) = action_line.items()
            idx = meta.get("_index", index)
            doc_id = meta.get("_id")
            routing = meta.get("routing", meta.get("_routing"))
            i += 1
            try:
                if action in ("index", "create"):
                    src = lines[i]; i += 1
                    res = self.index(idx, src, id=doc_id, routing=routing,
                                     op_type="create" if action == "create" else "index")
                    status = 201 if res.get("result") == "created" else 200
                    items.append({action: {**res, "status": status}})
                elif action == "delete":
                    try:
                        res = self.delete(idx, doc_id, routing=routing)
                        items.append({"delete": {**res, "status": 200}})
                    except ApiError as e:
                        if e.status != 404:
                            raise
                        items.append({"delete": {"_index": idx, "_id": doc_id,
                                                 "result": "not_found", "status": 404}})
                elif action == "update":
                    src = lines[i]; i += 1
                    res = self.update(idx, doc_id, src, routing=routing)
                    items.append({"update": {**res, "status": 200}})
                else:
                    raise ApiError(400, "illegal_argument_exception",
                                   f"unknown bulk action [{action}]")
                touched.add(idx)
            except ApiError as e:
                errors = True
                # the per-item error is reported in the response but the
                # request as a whole succeeds — count it or bulk failures
                # are invisible to dashboards (swallowed-exception audit)
                _iobs.count("indexing.bulk.item_failed")
                items.append({action: {"_index": idx, "_id": doc_id,
                                       "status": e.status, "error": e.body()["error"]}})
        if refresh:
            for idx in touched:
                try:
                    svc = self.node.get_index(
                        self.node.metadata.write_index(idx))
                except IndexNotFoundError:
                    continue
                with svc.write_lock:
                    svc.refresh()
        return {"took": 0, "errors": errors, "items": items}

    # ---------------- search APIs ----------------

    def search(self, index: str = "_all", body: Optional[dict] = None,
               scroll: Optional[str] = None, **kw) -> dict:
        with self.node.tracer.span("rest.search", index=index):
            body = dict(body or {})
            body.update({k: v for k, v in kw.items() if v is not None})
            # request deadline: the budget is anchored HERE, at REST accept,
            # so scheduler queue wait and every downstream stage spend from
            # the same clock (utils/deadline.py; docs/RESILIENCE.md)
            from ..utils import deadline as _ddl
            _dl_token = None
            if _ddl.current() is None:
                try:
                    _dl_obj = _ddl.Deadline.from_body(body)
                except ValueError as e:
                    raise ApiError(400, "parsing_exception", str(e))
                if _dl_obj is not None:
                    _dl_token = _ddl.set_current(_dl_obj)
            try:
                return self._search_deadlined(index, body, scroll)
            except _ddl.PartialResultsUnacceptable as e:
                raise ApiError(503, "search_phase_execution_exception", str(e))
            finally:
                if _dl_token is not None:
                    _ddl.reset_current(_dl_token)

    def _search_deadlined(self, index: str, body: dict,
                          scroll: Optional[str]) -> dict:
        # workload-group admission (reference wlm/): token-bucket rate
        # limit + resource-tracking QueryGroup enforcement
        group = body.pop("_workload_group", None)
        wg = self.node.wlm.group(group)
        try:
            # admission cost > 1 while the remediation actuator holds a
            # tighten_admission action (serving/remediator.py): the
            # token bucket contracts without any config mutation
            wg.admit_search(cost=self.node.remediation.wlm_cost())
        except PressureRejectedException as e:
            # a wlm admission 429 never reaches Node.search — record
            # the rejection against the query's shape here so admission
            # pressure is attributable per workload (obs/insights.py),
            # and mirror it into the ONE consistent rejection name
            # every admission layer shares (docs/SERVING.md)
            from ..obs import insights as _ins
            from ..utils.metrics import METRICS as _m
            _lane = getattr(wg, "lane", "interactive")
            _ins.INSIGHTS.record_rejection(body, _lane,
                                           source="wlm_admission")
            _m.counter(f"serving.lane.{_lane}.rejected").inc()
            raise _rejected_429(e)
        _wg_t0 = time.monotonic()
        if body.get("query") is not None:
            body["query"] = self._resolve_percolate_refs(body["query"])
        pit = body.pop("pit", None)
        # search pipeline: request param / inline body > index default
        sp_param = body.pop("search_pipeline", None)
        phase_ctx: dict = {}
        phase_hook = None
        pipeline = None
        try:
            pipeline = self.node.search_pipelines.resolve(
                sp_param, self._default_search_pipeline(index))
            if pipeline is not None:
                body = pipeline.transform_request(body, phase_ctx)
                phase_hook = pipeline.phase_hook()
        except SearchPipelineException as e:
            raise ApiError(400, "search_pipeline_exception", str(e))
        try:
            if pit is not None:
                resp = self._search_pit(pit, body, phase_hook=phase_hook,
                                        phase_ctx=phase_ctx)
                return self._apply_response_pipeline(pipeline, resp,
                                                     phase_ctx, body)
            # serving-scheduler lane: scroll-initiating searches ride the
            # batch lane; everything else inherits its workload group's
            # lane (interactive preempts batch at flush time)
            lane = ("batch" if scroll
                    else getattr(wg, "lane", "interactive"))
            # remediation admission (serving/remediator.py): while the
            # actuator holds shed actions, the body is re-fingerprinted
            # and matched against the alert's offending shapes — a shed
            # batch-lane shape 429s with Retry-After, an interactive
            # match is demoted to the batch lane for SCHEDULING only
            # (SLIs/insights keep the origin lane: deprioritization
            # must never hide a burn from the SLO that fired it).
            # Inert (one attribute read) while no action is engaged.
            sli_lane = lane
            try:
                lane = self.node.remediation.admit(body, lane)
            except PressureRejectedException as e:
                from ..obs import insights as _ins
                _ins.INSIGHTS.record_rejection(body, lane,
                                               source="remediation")
                raise _rejected_429(e)
            # flight recorder: the REST facade is where a request's
            # timeline begins (rest.accept + wlm lane classification);
            # Node.search reuses the ambient timeline and stamps the
            # engine-side events onto it
            from ..obs import flight_recorder as _fr
            _tl_token = None
            if _fr.RECORDER.enabled and not _fr.current():
                _tl = _fr.RECORDER.start("search", index=index,
                                         node=self.node.node_name)
                _tl_token = _fr.set_current(_tl)
                _fr.RECORDER.record(_tl, "rest.accept", index=index,
                                    group=wg.name, lane=lane)
            try:
                resp = self.node.search(
                    index, body, phase_hook=phase_hook,
                    phase_ctx=phase_ctx,
                    copy_protect=bool(pipeline is not None
                                      and pipeline.response_procs),
                    wlm_lane=lane, sli_lane=sli_lane)
            finally:
                if _tl_token is not None:
                    _fr.reset_current(_tl_token)
        except dsl.QueryParseError as e:
            # malformed DSL is a client error, not an engine crash
            raise ApiError(400, "parsing_exception", str(e))
        except CircuitBreakingException as e:
            raise ApiError(429, "circuit_breaking_exception", str(e))
        except TaskCancelledException as e:
            raise ApiError(400, "task_cancelled_exception", str(e))
        except IndexClosedError as e:
            raise ApiError(400, "index_closed_exception", str(e))
        except PressureRejectedException as e:
            # search backpressure admission control (reference
            # ratelimitting/admissioncontrol); scheduler queue-full
            # rejections carry a queue-depth-derived Retry-After
            raise _rejected_429(e)
        finally:
            # charge the group's resource tracker unconditionally — PIT
            # searches and searches that FAIL after consuming device time
            # must not bypass an enforced QueryGroup cap
            wg.record(time.monotonic() - _wg_t0)
        resp = self._apply_response_pipeline(pipeline, resp, phase_ctx, body)
        if scroll:
            sid = uuid.uuid4().hex
            names, remote_parts = self.node._split_remote_expression(index)
            snapshot = {n: [list(s.segments) for s in self.node.indices[n].shards]
                        for n in names}
            for alias, rnode, rnames in remote_parts:
                for rn in rnames:
                    snapshot[f"{alias}:{rn}"] = [
                        list(s.segments) for s in rnode.indices[rn].shards]
            ka = _parse_keepalive_s(scroll if scroll is not True else None)
            self._scrolls[sid] = {"index": index, "body": body,
                                  "offset": int(body.get("from", 0)) + int(body.get("size", 10)),
                                  "snapshot": snapshot,
                                  "keep_alive": ka,
                                  "expires": time.time() + ka}
            resp["_scroll_id"] = sid
        return resp

    def _default_search_pipeline(self, index: str) -> Optional[str]:
        """`index.search.default_pipeline` — applied only when the search
        targets a single concrete index (reference SearchPipelineService)."""
        try:
            names = self.node.metadata.resolve(index)
        except IndexNotFoundError:
            return None
        if len(names) != 1:
            return None
        s = self.node.indices[names[0]].meta.settings.get("index", {})
        return (s.get("search", {}).get("default_pipeline")
                or s.get("search.default_pipeline"))

    def _apply_response_pipeline(self, pipeline, resp: dict, phase_ctx: dict,
                                 body: dict) -> dict:
        """Mutates resp in place; node.search already deep-copied iff the
        response aliases a request-cache entry (copy_protect)."""
        if pipeline is None or not pipeline.response_procs:
            return resp
        try:
            return pipeline.transform_response(resp, phase_ctx, body)
        except SearchPipelineException as e:
            raise ApiError(400, "search_pipeline_exception", str(e))

    # ---------------- search pipeline CRUD (reference _search/pipeline) ----

    def put_search_pipeline(self, id: str, body: dict) -> dict:
        try:
            self.node.search_pipelines.put(id, body)
        except SearchPipelineException as e:
            raise ApiError(400, "search_pipeline_exception", str(e))
        return {"acknowledged": True}

    def get_search_pipeline(self, id: Optional[str] = None) -> dict:
        try:
            return self.node.search_pipelines.get(id)
        except SearchPipelineException as e:
            raise ApiError(404, "resource_not_found_exception", str(e))

    def delete_search_pipeline(self, id: str) -> dict:
        try:
            self.node.search_pipelines.delete(id)
        except SearchPipelineException as e:
            raise ApiError(404, "resource_not_found_exception", str(e))
        return {"acknowledged": True}

    def _resolve_percolate_refs(self, node):
        """Inline stored-document references before parsing:
        - `{"percolate": {"index", "id"}}` fetches the candidate doc
          (reference TransportPercolateQuery GET step);
        - `{"geo_shape": {field: {"indexed_shape": {index, id, path}}}}`
          fetches the pre-indexed shape (reference GeoShapeQueryBuilder
          circuit through the get action).
        Pure: returns a copied tree; never descends into percolate bodies
        (candidate documents are user content, not DSL)."""
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k == "percolate" and isinstance(v, dict):
                    if ("document" not in v and "documents" not in v
                            and v.get("index") and v.get("id")):
                        got = self.get(v["index"], v["id"],
                                       routing=v.get("routing"))
                        v = dict(v)
                        v["document"] = got.get("_source", {})
                    out[k] = v
                elif k == "geo_shape" and isinstance(v, dict):
                    out[k] = {fk: self._resolve_indexed_shape(fv)
                              for fk, fv in v.items()}
                else:
                    out[k] = self._resolve_percolate_refs(v)
            return out
        if isinstance(node, list):
            return [self._resolve_percolate_refs(v) for v in node]
        return node

    def _resolve_indexed_shape(self, spec):
        if not (isinstance(spec, dict) and isinstance(
                spec.get("indexed_shape"), dict)):
            return spec
        ref = spec["indexed_shape"]
        if not (ref.get("index") and ref.get("id")):
            raise ApiError(400, "parsing_exception",
                           "[geo_shape] indexed_shape needs [index] and [id]")
        try:
            got = self.get(ref["index"], ref["id"],
                           routing=ref.get("routing"))
        except (ApiError, IndexNotFoundError):
            raise ApiError(400, "illegal_argument_exception",
                           f"indexed shape [{ref['index']}/{ref['id']}] "
                           f"not found")
        src = got.get("_source", {})
        shape = src
        for part in str(ref.get("path", "shape")).split("."):
            shape = shape.get(part) if isinstance(shape, dict) else None
        if shape is None:
            raise ApiError(400, "illegal_argument_exception",
                           f"shape path [{ref.get('path', 'shape')}] not "
                           f"found in indexed document")
        out = {fk: fv for fk, fv in spec.items() if fk != "indexed_shape"}
        out["shape"] = shape
        return out

    def _snapshot_searchers(self, snapshot: Dict[str, list]) -> List[ShardSearcher]:
        """Searchers bound to a scroll/PIT segment snapshot ("alias:index"
        keys resolve through the registered remote cluster)."""
        searchers = []
        for n, shard_segs in snapshot.items():
            node = self.node
            name = n
            if ":" in n and n.split(":", 1)[0] in self.node.remote_clusters:
                alias, name = n.split(":", 1)
                node = self.node.remote_clusters[alias]
            svc = node.indices.get(name)
            if svc is None:
                continue
            for sid, segs in enumerate(shard_segs):
                s = ShardSearcher(svc.shards[sid], shard_id=sid,
                                  similarity=svc.default_sim, index_key=n)
                s._snapshot_segments = segs
                searchers.append(s)
        return searchers

    def _expire_contexts(self) -> None:
        """Lazy keep-alive enforcement (reference: reaper thread)."""
        now = time.time()
        for sid in [k for k, v in self._scrolls.items()
                    if v.get("expires", now + 1) <= now]:
            del self._scrolls[sid]
        for pid in [k for k, v in self._pits.items()
                    if v.get("expires", now + 1) <= now]:
            del self._pits[pid]

    def scroll(self, scroll_id: str, scroll: Optional[str] = None) -> dict:
        self._expire_contexts()
        sctx = self._scrolls.get(scroll_id)
        if sctx is None:
            raise ApiError(404, "search_context_missing_exception",
                           f"No search context found for id [{scroll_id}]")
        ka = (_parse_keepalive_s(scroll) if scroll
              else sctx.get("keep_alive", 60.0))
        sctx["keep_alive"] = ka
        sctx["expires"] = time.time() + ka
        body = dict(sctx["body"])
        body["from"] = sctx["offset"]
        searchers = self._snapshot_searchers(sctx["snapshot"])
        resp = _search_snapshot(searchers, body, sctx["index"])
        sctx["offset"] += int(body.get("size", 10))
        resp["_scroll_id"] = scroll_id
        return resp

    def clear_scroll(self, scroll_id=None, body: Optional[dict] = None) -> dict:
        ids = []
        if scroll_id:
            ids = scroll_id if isinstance(scroll_id, list) else [scroll_id]
        if body:
            bid = body.get("scroll_id", [])
            ids.extend(bid if isinstance(bid, list) else [bid])
        if any(sid in ("_all", "*") for sid in ids):
            n = len(self._scrolls)
            self._scrolls.clear()
            return {"succeeded": True, "num_freed": n}
        n = 0
        for sid in ids:
            if self._scrolls.pop(sid, None) is not None:
                n += 1
        return {"succeeded": True, "num_freed": n}

    def create_pit(self, index: str, keep_alive: str = "1m") -> dict:
        """Point-in-time reader: snapshot of the immutable segment lists
        (reference `action/search/CreatePitAction` — free with immutability)."""
        pid = uuid.uuid4().hex
        names = self.node.metadata.resolve(index)
        snapshot = {n: [list(s.segments) for s in self.node.indices[n].shards]
                    for n in names}
        ka = _parse_keepalive_s(keep_alive)
        self._pits[pid] = {"index": index, "snapshot": snapshot,
                           "creation_time": time.time(),
                           "keep_alive": ka,
                           "expires": time.time() + ka}
        return {"pit_id": pid, "creation_time": int(time.time() * 1000)}

    def delete_pit(self, body: dict) -> dict:
        ids = body.get("pit_id", [])
        ids = ids if isinstance(ids, list) else [ids]
        deleted = [p for p in ids if self._pits.pop(p, None) is not None]
        return {"pits": [{"pit_id": p, "successful": True} for p in deleted]}

    def _search_pit(self, pit: dict, body: dict, phase_hook=None,
                    phase_ctx: Optional[dict] = None) -> dict:
        pit_id = pit["id"]
        self._expire_contexts()
        pctx = self._pits.get(pit_id)
        if pctx is None:
            raise ApiError(404, "search_context_missing_exception",
                           f"Point in time [{pit_id}] not found")
        # per-request keep_alive extends the context (reference behavior)
        ka = (_parse_keepalive_s(pit["keep_alive"])
              if pit.get("keep_alive") else pctx.get("keep_alive", 60.0))
        pctx["keep_alive"] = ka
        pctx["expires"] = time.time() + ka
        searchers = self._snapshot_searchers(pctx["snapshot"])
        resp = _search_snapshot(searchers, body, pctx["index"],
                                phase_hook=phase_hook, phase_ctx=phase_ctx)
        resp["pit_id"] = pit_id
        return resp

    def msearch(self, body: List[dict], index: Optional[str] = None) -> dict:
        with self.node.tracer.span("rest.msearch", lines=len(body)):
            pairs = []
            i = 0
            while i < len(body):
                header = body[i]; i += 1
                search_body = body[i]; i += 1
                pairs.append((header.get("index", index or "_all"), search_body))
            # batched TPU path: one index expression -> fast-path-eligible
            # bodies fuse into grouped Pallas kernel launches (grid over
            # queries); the rest come back as None and run per-body below.
            # A search pipeline (explicit or index default) forces the
            # per-body path so each body gets its processors applied
            partial: List[Optional[dict]] = [None] * len(pairs)
            if (pairs and len({idx for idx, _ in pairs}) == 1
                    and not any("search_pipeline" in b or "_workload_group" in b
                                for _, b in pairs)
                    and not self._default_search_pipeline(pairs[0][0])):
                try:
                    resps = self.node.msearch(pairs[0][0],
                                              [b for _, b in pairs])
                except (dsl.QueryParseError, IndexNotFoundError, IndexClosedError,
                        KeyError, TypeError, ValueError, CircuitBreakingException):
                    # fall back to the per-body path, which maps errors into
                    # per-response error objects
                    resps = None
                if resps is not None:
                    partial = list(resps)
            todo = [i for i, r in enumerate(partial) if r is None]

            def run_one(i: int) -> dict:
                idx, search_body = pairs[i]
                try:
                    return self.search(idx, search_body)
                except (ApiError, IndexNotFoundError) as e:
                    return {"error": {"type": type(e).__name__,
                                      "reason": str(e)}}

            if len(todo) > 1:
                # concurrent per-body fallback (reference
                # TransportMultiSearchAction runs items concurrently too):
                # device steps serialize but host work and device round trips
                # overlap across bodies. Runs on the node's named "search"
                # pool (utils/threadpool.py) instead of a throwaway executor —
                # bounded node-wide, counted in _nodes/stats, and the pool's
                # contextvars carry the request's trace span into the workers
                futs = [(i, self.node.thread_pools.pool("search").submit(
                    run_one, i)) for i in todo]
                for i, fut in futs:
                    partial[i] = fut.result()
            else:
                for i in todo:
                    partial[i] = run_one(i)
            for _, b in pairs:
                if isinstance(b, dict):
                    # internal mesh-decline marker must not leak into the
                    # caller's body dicts (bodies served by the batched kernel
                    # path never traverse Node.search, which pops it)
                    b.pop("_mesh_declined", None)
            return {"took": 0, "responses": partial}

    # ------ _remotestore/_restore (reference RestoreRemoteStoreAction) -----

    def remotestore_restore(self, body: dict) -> dict:
        """POST /_remotestore/_restore analog: re-materialize indices from
        the node's remote-backed storage mirror. Indices must not exist
        locally (delete/lose them first) — mirroring the reference's
        closed-or-absent requirement."""
        from ..cluster.state import (ClusterStateError, IndexNotFoundError,
                                     ResourceAlreadyExistsError)
        names = body.get("indices", [])
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",") if n.strip()]
        if not names:
            raise ApiError(400, "action_request_validation_exception",
                           "indices is required")
        out = []
        for name in names:
            try:
                out.append(self.node.restore_from_remote(name))
            except ResourceAlreadyExistsError as e:
                raise ApiError(400, "illegal_argument_exception", str(e))
            except IndexNotFoundError as e:
                raise ApiError(404, "index_not_found_exception", str(e))
            except ClusterStateError as e:
                raise ApiError(400, "illegal_argument_exception", str(e))
        return {"remote_store": {"accepted": True, "indices": out}}

    # ---------------- _validate/query (reference ValidateQueryAction) ------

    def validate_query(self, index: str = "_all",
                       body: Optional[dict] = None,
                       explain: bool = False,
                       rewrite: bool = False) -> dict:
        """Parse AND rewrite the query against every resolved index without
        executing it — the verdict never depends on the display flags
        (explain/rewrite only add per-index explanation entries)."""
        body = body or {}
        try:
            names = self.node.metadata.resolve(index)
        except IndexNotFoundError as e:
            raise ApiError(404, "index_not_found_exception", str(e))
        try:
            q = dsl.parse_query(body.get("query", {"match_all": {}}))
        except ValueError as e:   # QueryParseError is a ValueError
            out = {"valid": False,
                   "_shards": {"total": 1, "successful": 1, "failed": 0}}
            if explain:
                out["explanations"] = [{"index": n, "valid": False,
                                        "error": str(e)} for n in names] \
                    or [{"index": index, "valid": False, "error": str(e)}]
            return out
        explanations = []
        all_valid = True
        for n in names:
            svc = self.node.indices[n]
            segs = [s for sh in svc.shards for s in sh.segments]
            ctx = PL.ShardContext(svc.mappings, segs, svc.default_sim)
            try:
                detail = C.describe_plan(PL.rewrite(q, ctx, scoring=True))
                explanations.append({
                    "index": n, "valid": True,
                    "explanation":
                        f"{detail['type']}({detail['description']})"})
            except ValueError as e:
                all_valid = False
                explanations.append({"index": n, "valid": False,
                                     "error": str(e)})
        out = {"valid": all_valid,
               "_shards": {"total": len(names) or 1,
                           "successful": len(names) or 1, "failed": 0}}
        if explain or rewrite:
            out["explanations"] = explanations
        return out

    # ---------------- cross-cluster search (reference RemoteClusterService)

    def put_remote_cluster(self, alias: str, remote) -> dict:
        """Register a peer cluster for "alias:index" expressions. `remote`
        is another RestClient or Node (in-process peers — the HTTP-less
        analog of `cluster.remote.<alias>.seeds`)."""
        node = getattr(remote, "node", remote)
        if node is self.node:
            raise ApiError(400, "illegal_argument_exception",
                           "cannot register a cluster with itself")
        self.node.remote_clusters[alias] = node
        return {"acknowledged": True}

    def delete_remote_cluster(self, alias: str) -> dict:
        if self.node.remote_clusters.pop(alias, None) is None:
            raise ApiError(404, "resource_not_found_exception",
                           f"remote cluster [{alias}] not found")
        return {"acknowledged": True}

    def remote_info(self) -> dict:
        """GET _remote/info shape."""
        return {alias: {"connected": True, "mode": "in_process",
                        "num_indices": len(n.indices),
                        "cluster_name": n.metadata.cluster_name}
                for alias, n in self.node.remote_clusters.items()}

    # ---------------- node stats + tracing (reference _nodes/stats) --------

    def nodes_stats(self) -> dict:
        """Full per-node stats rollup (reference NodesStatsResponse):
        indices totals + op counters, process mem/cpu, fs, pools,
        breakers, caches, pipelines, wlm, tracing."""
        import resource
        import shutil
        import sys
        n = self.node
        ru = resource.getrusage(resource.RUSAGE_SELF)
        # ru_maxrss: bytes on macOS, KiB on Linux
        rss_mult = 1 if sys.platform == "darwin" else 1024
        try:
            du = shutil.disk_usage(n.data_path or "/")
            fs = {"total": {"total_in_bytes": du.total,
                            "free_in_bytes": du.free,
                            "available_in_bytes": du.free}}
        except OSError:
            fs = {}
        summ = self.indices_summary()
        docs = summ["docs"]
        store = summ["store_in_bytes"]
        seg_count = summ["segments"]
        oc = n.op_counters
        node_block = {
            "name": n.node_name,
            "roles": ["cluster_manager", "data", "ingest"],
            "indices": {
                "docs": {"count": docs},
                "store": {"size_in_bytes": store},
                "segments": {"count": seg_count},
                "search": {"query_total": oc["search_total"],
                           "query_time_in_millis":
                               int(oc["search_time_ms"])},
                "indexing": {"index_total": oc["index_total"],
                             "index_time_in_millis":
                                 int(oc["index_time_ms"])},
                "get": {"total": oc["get_total"]},
                "request_cache": n.request_cache.stats(),
            },
            "process": {
                "mem": {"resident_set_size_in_bytes":
                        ru.ru_maxrss * rss_mult},
                "cpu": {"total_in_millis":
                        int((ru.ru_utime + ru.ru_stime) * 1000)},
            },
            "fs": fs,
            "thread_pool": n.thread_pools.stats(),
            "breakers": n.breakers.stats(),
            "tasks": n.tasks.stats(),
            "wlm": n.wlm.stats(),
            "search_backpressure": n.search_backpressure.stats(),
            # serving scheduler (serving/scheduler.py): queue depth,
            # batch-size / queue-wait percentiles, flush reasons, lanes
            "serving": n.serving.stats(),
            "search_pipelines": n.search_pipelines.stats(),
            "tracing": n.tracer.stats(),
            # flight recorder (obs/flight_recorder.py): ring occupancy,
            # timelines, anomaly-trigger counts, recent dump metadata
            "flight_recorder": n.flight_recorder.stats(),
            # HBM ledger (obs/hbm_ledger.py): attributed device-memory
            # residency by tenant kind, peaks, and breaker-derivation
            # counters — the byte-domain companion to the breakers block.
            # On silicon the snapshot carries the device allocator
            # cross-check (drift beyond threshold has already fired a
            # flight-recorder hbm_drift dump)
            "hbm": self._hbm_block(),
            # device query-phase telemetry: kernel serve/fallback counters
            # incl. pruned-path escalations (the pruning design is only as
            # good as its escalation rate), and the SPMD mesh dispatch
            # share when a mesh service is attached
            "fastpath": dict(_fastpath.STATS),
            # where the phase-2 candidate-union rescore ran and what it
            # cost (host numpy fallback vs batched device launches)
            "fastpath_rescore": _fastpath.rescore_stats(),
            # codec-v2 eager-impact path (search/impactpath.py): serve /
            # escalation ladder counters plus the device block-skip rate
            # (blocks the block-max prune never gathered)
            "impactpath": self._impactpath_block(),
            # hybrid retrieval (search/fusion.py): fused searches by
            # method, sub-query volume, and the coalesced pure-knn batch
            # launch counters (executor._launch_knn_segment)
            "hybridpath": self._hybridpath_block(),
            # unified telemetry (utils/metrics.py): per-stage latency
            # percentiles for every instrumented stage (search phases,
            # fastpath ladder rungs, mesh dispatch, distnode RPCs) and
            # the jit program-cache / compile-vs-execute attribution
            "telemetry": self._telemetry_block(),
            # fault tolerance (docs/RESILIENCE.md): distnode RPC retry /
            # failover / deadline counters, backoff percentiles, and the
            # chaos-harness installation state (cluster/faults.py).
            # Process-global like /_metrics — co-resident test nodes
            # share the rollup
            "resilience": self._resilience_block(),
            # time-series retention ring (obs/timeseries.py): sampler
            # state behind `_nodes/stats/history`
            "timeseries": n.timeseries.stats(),
            # SLO burn-rate engine (obs/slo.py): armed objectives, live
            # burn rates and alert counts (full view at GET /_slo)
            "slo": n.slo.stats(),
            # query insights (obs/insights.py): workload fingerprint
            # sketch occupancy (full view at GET /_insights/top_queries)
            "insights": n.insights.stats(),
            # remediation actuator (serving/remediator.py): live action
            # count + engage/shed totals (full view at GET /_remediation)
            "remediation": n.remediation.stats(),
            # ingest observatory (obs/ingest_obs.py): the whole write
            # path — bulk accept, pipelines, writer buffer, refresh with
            # stage attribution + refresh-to-visible, merge + reorder,
            # flush, translog, replica fan-out. Federated fleet-wide by
            # `DistClusterNode.indexing_stats` (summed counters, MERGED
            # sketches — percentiles never averaged)
            "indexing": self._indexing_block(),
        }
        if n.mesh_service is not None:
            node_block["mesh"] = n.mesh_service.stats()
        return {"cluster_name": n.metadata.cluster_name,
                "nodes": {n.node_name: node_block}}

    @staticmethod
    def _indexing_block() -> dict:
        return _iobs.assemble_block(_iobs.local_parts())

    @staticmethod
    def _impactpath_block() -> dict:
        from ..search import impactpath as _ip
        out = _ip.stats()
        out["block_skip_rate"] = round(_ip.block_skip_rate(), 4)
        return out

    @staticmethod
    def _hybridpath_block() -> dict:
        from ..search import fusion as _fusion
        return _fusion.stats()

    def _hbm_block(self) -> dict:
        out = self.node.hbm_ledger.snapshot()
        try:
            check = self.node.hbm_ledger.check_device()
        except Exception:           # stats probe must never fail a read
            check = None
        if check is not None:
            out["device_check"] = check
        return out

    @staticmethod
    def _resilience_block() -> dict:
        from ..cluster import faults as _faults
        from ..utils.metrics import METRICS

        def c(name):
            return METRICS.counter(name).value
        return {
            "rpc": {"failed": c("dist.rpc.failed"),
                    "retries": c("dist.rpc.retry"),
                    "failovers": c("dist.rpc.failover"),
                    "backoff_ms": METRICS.percentiles(
                        "dist.rpc.backoff_ms")},
            "deadline": {"exhausted": c("dist.deadline.exhausted"),
                         "expired_on_arrival":
                             c("dist.deadline.expired_on_arrival")},
            "shards_failed": c("dist.shard_failed"),
            "publish_failed": c("dist.publish.failed"),
            "refresh_failed": c("dist.refresh.failed"),
            "chaos": _faults.stats(),
        }

    @staticmethod
    def _telemetry_block() -> dict:
        from ..search import compiler as _compiler
        from ..utils.metrics import METRICS
        return {"stages": METRICS.stage_percentiles(),
                "jit": _compiler.jit_attribution()}

    # ------------- fleet observability (docs/OBSERVABILITY.md "fleet") ----

    def indices_summary(self) -> dict:
        """Node-local index totals — one scrape leg of `_cluster/stats`
        (and the `_nodes/stats` indices rollup above)."""
        docs = store = seg_count = 0
        for svc in self.node.indices.values():
            st = svc.stats()
            docs += st["docs"]["count"]
            store += st["store"]["size_in_bytes"]
            seg_count += st["segments"]["count"]
        return {"docs": docs, "store_in_bytes": store,
                "segments": seg_count}

    def cluster_stats(self) -> dict:
        """`GET /_cluster/stats` on an UNclustered node: the same shape
        the distnode federation serves (cluster/distnode.py
        `cluster_stats`), degenerated to a fleet of one — so dashboards
        and tests read one schema everywhere."""
        from ..utils.metrics import METRICS, sketch_snapshot
        wire = METRICS.to_wire()
        name = self.node.node_name
        indices = self.indices_summary()
        return {
            "cluster_name": self.node.metadata.cluster_name,
            "coordinator": name,
            "_nodes": {"total": 1, "successful": 1, "failed": 0},
            "nodes": {name: {"status": "ok",
                             "gauges": wire["gauges"],
                             "counters": wire["counters"],
                             "indices": indices}},
            "indices": indices,
            "counters": wire["counters"],
            "percentiles": {k: sketch_snapshot(w)
                            for k, w in wire["histograms"].items()},
            "histograms": wire["histograms"],
        }

    def metrics_history(self, metric: str, window_s: float = 60.0) -> dict:
        """`GET /_nodes/stats/history` on an unclustered node: the local
        sampler's window for one metric, in the federated response
        shape (obs/timeseries.py)."""
        name = self.node.node_name
        return {"metric": metric, "window_s": float(window_s),
                "_nodes": {"total": 1, "successful": 1, "failed": 0},
                "nodes": {name: self.node.timeseries.history(
                    metric, window_s)}}

    def slo_status(self) -> dict:
        """`GET /_slo`: armed objectives, live burn rates, alert log
        (obs/slo.py)."""
        return self.node.slo.status()

    def insights_top_queries(self, by: str = "latency", n: int = 10,
                             window_s: Optional[float] = None) -> dict:
        """`GET /_insights/top_queries` on an UNclustered node: the
        same schema the distnode federation serves (cluster/distnode.py
        `top_queries_federated`), degenerated to a fleet of one."""
        from ..obs import insights as _ins
        eng = self.node.insights
        try:
            top = eng.top(by=by, n=n, window_s=window_s)
        except ValueError as e:
            raise ApiError(400, "illegal_argument_exception", str(e))
        name = self.node.node_name
        return {"by": by, "n": int(n),
                **({"window_s": float(window_s)}
                   if window_s is not None else {}),
                "capacity": eng.capacity,
                "total_records": eng.sketch.total_records,
                "_nodes": {"total": 1, "successful": 1, "failed": 0},
                "nodes": {name: {"status": "ok"}},
                "top_queries": top}

    def insights_status(self) -> dict:
        """`GET /_insights`: engine state (capacity, entries,
        evictions, window occupancy)."""
        return {"insights": self.node.insights.stats()}

    def remediation_status(self) -> dict:
        """`GET /_remediation` on an UNclustered node: the same schema
        the distnode federation serves (cluster/distnode.py
        `remediation_federated`), degenerated to a fleet of one."""
        name = self.node.node_name
        return {"_nodes": {"total": 1, "successful": 1, "failed": 0},
                "nodes": {name: {"status": "ok",
                                 **self.node.remediation.status()}}}

    def get_traces(self, limit: int = 20) -> dict:
        """Recent completed request traces (reference telemetry in-memory
        span exporter shape)."""
        return {"traces": self.node.tracer.traces(limit)}

    # ------------- flight recorder + hot threads (obs/) -------------

    def flight_recorder(self, dumps: int = 5) -> dict:
        """`GET /_flight_recorder`: ring stats + the most recent dump
        bundles (full timelines, newest first)."""
        rec = self.node.flight_recorder
        return {"recorder": rec.stats(), "dumps": rec.dumps(limit=dumps)}

    def flight_recorder_dump(self, note: Optional[str] = None) -> dict:
        """`POST /_flight_recorder/dump`: manual snapshot — freeze every
        timeline currently in the ring into one bundle."""
        rec = self.node.flight_recorder
        if not rec.enabled:
            raise ApiError(400, "illegal_argument_exception",
                           "flight recorder is disabled on this node")
        bundle = rec.trigger("manual", None, note=note, force=True)
        return {"acknowledged": True, "dump": bundle}

    def hot_threads(self, snapshots: int = 3, interval_ms: float = 20.0,
                    ignore_idle: bool = True, as_json: bool = False):
        """`GET /_nodes/hot_threads`: live Python stacks of the runtime's
        worker threads (serving dispatcher/completion, named pools, HTTP
        request threads), idle-filtered, sampled `snapshots` times."""
        from ..obs.hot_threads import hot_threads as _ht
        return _ht(node_name=self.node.node_name, snapshots=snapshots,
                   interval_s=interval_ms / 1000.0,
                   ignore_idle=ignore_idle, as_json=as_json)

    # ---------------- tasks API (reference action/admin/cluster/node/tasks) --

    def tasks(self, actions: Optional[str] = None) -> dict:
        return {"nodes": {self.node.node_name: {
            "tasks": {str(t["id"]): t
                      for t in self.node.tasks.list(actions)}}}}

    def cancel_task(self, task_id, reason: str = "by user request") -> dict:
        try:
            tid = int(str(task_id).rsplit(":", 1)[-1])
        except ValueError:
            raise ApiError(404, "resource_not_found_exception",
                           f"task [{task_id}] is not found")
        ok = self.node.tasks.cancel(tid, reason)
        if not ok:
            raise ApiError(404, "resource_not_found_exception",
                           f"task [{task_id}] is not found or not cancellable")
        return {"acknowledged": True}

    # ---------------- lifecycle + workload management ----------------

    def put_lifecycle_policy(self, name: str, body: dict) -> dict:
        try:
            self.node.lifecycle.put_policy(name, body or {})
        except ValueError as e:
            raise ApiError(400, "illegal_argument_exception", str(e))
        return {"acknowledged": True}

    def get_lifecycle_policy(self, name: str) -> dict:
        p = self.node.lifecycle.get_policy(name)
        if p is None:
            raise ApiError(404, "resource_not_found_exception",
                           f"lifecycle policy [{name}] not found")
        return {name: {"policy": p}}

    def lifecycle_explain(self, index: str) -> dict:
        from ..cluster.state import ClusterStateError
        try:
            return self.node.lifecycle.explain(
                self.node.metadata.write_index(index))
        except ClusterStateError as e:
            raise ApiError(400, "illegal_argument_exception", str(e))

    def lifecycle_step(self, now: Optional[float] = None) -> dict:
        """One deterministic ISM tick (the reference runs this on a
        scheduler; callers own the clock here)."""
        return {"actions": self.node.lifecycle.step(now)}

    def rollover(self, alias: str, body: Optional[dict] = None) -> dict:
        """_rollover: roll the alias's (or data stream's) write index when
        ANY condition is met (empty conditions = always; reference
        RolloverRequest)."""
        body = body or {}
        if alias in self.node.metadata.data_streams:
            from ..cluster import datastream as dstream
            old = self.node.metadata.write_index(alias)
            conds = body.get("conditions", {})
            try:
                results = self.node.lifecycle.check_conditions(old, conds)
            except ValueError as e:
                raise ApiError(400, "illegal_argument_exception", str(e))
            rolled = (not conds) or any(results.values())
            if not rolled:
                return {"acknowledged": False, "rolled_over": False,
                        "old_index": old, "new_index": None,
                        "conditions": results}
            out = _map_ds_errors(dstream.rollover_data_stream, self.node,
                                 alias)
            out["conditions"] = results
            return out
        if alias not in self.node.metadata.aliases:
            raise ApiError(400, "illegal_argument_exception",
                           f"rollover target [{alias}] is not an alias")
        from ..cluster.state import ClusterStateError
        try:
            old = self.node.metadata.write_index(alias)
        except ClusterStateError as e:
            raise ApiError(400, "illegal_argument_exception", str(e))
        conds = body.get("conditions", {})
        try:
            results = self.node.lifecycle.check_conditions(old, conds)
        except ValueError as e:
            raise ApiError(400, "illegal_argument_exception", str(e))
        rolled = (not conds) or any(results.values())
        new_index = None
        if rolled:
            new_index = self.node.lifecycle.rollover(alias, old)
        return {"acknowledged": rolled, "rolled_over": rolled,
                "old_index": old, "new_index": new_index,
                "conditions": results}

    def put_workload_group(self, name: str, body: Optional[dict] = None) -> dict:
        body = body or {}
        try:
            self.node.wlm.put_group(name, body.get("search_rate"),
                                    body.get("search_burst"),
                                    body.get("resource_limits"),
                                    body.get("mode", "monitor"),
                                    body.get("lane", "interactive"))
        except ValueError as e:
            raise ApiError(400, "illegal_argument_exception", str(e))
        return {"acknowledged": True}

    # ---------------- search templates (reference modules/lang-mustache) ----

    def put_script(self, id: str, body: dict) -> dict:
        """PUT _scripts/{id}: store a search template / script."""
        script = body.get("script", body)
        self._stored_scripts[id] = script.get("source", script)
        return {"acknowledged": True}

    def get_script(self, id: str) -> dict:
        src = self._stored_scripts.get(id)
        if src is None:
            raise ApiError(404, "resource_not_found_exception",
                           f"unable to find script [{id}]")
        return {"_id": id, "found": True,
                "script": {"lang": "mustache", "source": src}}

    def delete_script(self, id: str) -> dict:
        if self._stored_scripts.pop(id, None) is None:
            raise ApiError(404, "resource_not_found_exception",
                           f"unable to find script [{id}]")
        return {"acknowledged": True}

    def _resolve_template(self, body: dict) -> dict:
        from .templates import TemplateError, render_template
        if body.get("id") is not None:
            src = self._stored_scripts.get(body["id"])
            if src is None:
                raise ApiError(404, "resource_not_found_exception",
                               f"unable to find script [{body['id']}]")
        else:
            src = body.get("source")
            if src is None:
                raise ApiError(400, "action_request_validation_exception",
                               "template is missing")
        try:
            return render_template(src, body.get("params"))
        except TemplateError as e:
            raise ApiError(400, "parsing_exception", str(e))

    def search_template(self, index: str = "_all",
                        body: Optional[dict] = None) -> dict:
        rendered = self._resolve_template(body or {})
        return self.search(index, rendered)

    def render_search_template(self, body: Optional[dict] = None) -> dict:
        return {"template_output": self._resolve_template(body or {})}

    def msearch_template(self, body: List[dict],
                         index: Optional[str] = None) -> dict:
        lines = []
        i = 0
        while i < len(body):
            header = body[i]; i += 1
            tmpl = body[i]; i += 1
            lines.append(header)
            try:
                lines.append(self._resolve_template(tmpl))
            except ApiError as e:
                lines.append({"_template_error": str(e)})
        msb = []
        for j in range(0, len(lines), 2):
            if "_template_error" not in lines[j + 1]:
                msb += [lines[j], lines[j + 1]]
        sub = self.msearch(msb, index=index)["responses"] if msb else []
        responses = []
        si = 0
        for j in range(0, len(lines), 2):
            if "_template_error" in lines[j + 1]:
                responses.append({"error": {
                    "type": "parsing_exception",
                    "reason": lines[j + 1]["_template_error"]}})
            else:
                responses.append(sub[si])
                si += 1
        return {"took": 0, "responses": responses}

    def rank_eval(self, index: str = "_all",
                  body: Optional[dict] = None) -> dict:
        """POST {index}/_rank_eval (reference modules/rank-eval)."""
        from ..search.rank_eval import run_rank_eval
        try:
            return run_rank_eval(self, index, body or {})
        except dsl.QueryParseError as e:
            raise ApiError(400, "parsing_exception", str(e))

    def count(self, index: str = "_all", body: Optional[dict] = None) -> dict:
        body = dict(body or {})
        body["size"] = 0
        body.pop("sort", None)
        if body.get("query") is not None:
            body["query"] = self._resolve_percolate_refs(body["query"])
        resp = self.node.search(index, body)
        return {"count": resp["hits"]["total"]["value"],
                "_shards": resp["_shards"]}

    def explain(self, index: str, id: str, body: dict) -> dict:
        svc = self.node.get_index(self.node.metadata.write_index(index))
        eng = svc.route(id)
        eng_refresh_needed = id in {d.doc_id for d in eng.buffer if d is not None}
        if eng_refresh_needed:
            eng.refresh()
        loc = eng.version_map.get(id)
        if loc is None or loc.in_buffer:
            raise ApiError(404, "document_missing_exception", f"[{id}] missing")
        seg, doc = loc.segment, loc.local_doc
        ctx = PL.ShardContext(svc.mappings, eng.segments, svc.default_sim)
        qdict = (self._resolve_percolate_refs(body["query"])
                 if body.get("query") is not None else None)
        lroot = PL.rewrite(dsl.parse_query(qdict), ctx, scoring=True)
        expl = explain_doc(lroot, seg, doc, ctx)
        return {"_index": svc.meta.name, "_id": id,
                "matched": expl["value"] > 0, "explanation": expl}

    def field_caps(self, index: str = "_all", fields: str = "*") -> dict:
        names = self.node.metadata.resolve(index)
        pats = fields if isinstance(fields, list) else fields.split(",")
        import fnmatch as fn
        out: Dict[str, dict] = {}
        for n in names:
            svc = self.node.indices[n]
            allf = dict(svc.mappings.fields)
            for f, ft in list(allf.items()):
                for sub, sft in ft.subfields.items():
                    allf[f"{f}.{sub}"] = sft
            for f, ft in allf.items():
                if not any(fn.fnmatch(f, p) for p in pats):
                    continue
                caps = out.setdefault(f, {}).setdefault(ft.type, {
                    "type": ft.type, "searchable": ft.index,
                    "aggregatable": ft.doc_values or ft.type == "text"})
        return {"indices": names, "fields": out}

    def termvectors(self, index: str, id: Optional[str] = None,
                    body: Optional[dict] = None,
                    fields: Optional[List[str]] = None,
                    term_statistics: bool = False,
                    field_statistics: bool = True,
                    positions: bool = True, offsets: bool = True) -> dict:
        """Reference `action/termvectors/TermVectorsRequest.java`: real doc
        or artificial (`body["doc"]`), per-term tokens with positions/
        offsets, optional term statistics (doc_freq/ttf across the index's
        segments), field statistics, and the tf-idf `filter` block."""
        body = body or {}
        fields = fields or body.get("fields")
        term_statistics = bool(body.get("term_statistics", term_statistics))
        field_statistics = bool(body.get("field_statistics",
                                         field_statistics))
        positions = bool(body.get("positions", positions))
        offsets = bool(body.get("offsets", offsets))
        tv_filter = body.get("filter") or {}
        svc = self.node.get_index(self.node.metadata.write_index(index))
        if body.get("doc") is not None:
            src = body["doc"]
            found = True
            resp_id = id or ""
        else:
            if id is None:
                raise ApiError(400, "action_request_validation_exception",
                               "termvectors needs an [id] or a [doc]")
            try:
                doc = self.get(index, id)
            except ApiError:
                return {"_index": svc.meta.name, "_id": id, "found": False}
            src = doc["_source"]
            found = True
            resp_id = id
        segs = [s for sh in svc.shards for s in sh.segments]

        def _stats(fname: str, term: str):
            df = ttf = 0
            for s in segs:
                pb = s.postings.get(fname)
                if pb is None:
                    continue
                r = pb.row(term)
                if r >= 0:
                    a, b = int(pb.starts[r]), int(pb.starts[r + 1])
                    df += b - a
                    ttf += int(pb.tfs[a:b].sum())
            return df, ttf

        out_fields = {}
        for fname, ft in list(svc.mappings.fields.items()):
            if ft.type not in ("text", "keyword", "annotated_text") or \
                    (fields and fname not in fields):
                continue
            vals = _get_source_path(src, fname)
            if vals is None:
                continue
            terms: Dict[str, dict] = {}
            for v in (vals if isinstance(vals, list) else [vals]):
                if ft.type == "keyword":
                    t = terms.setdefault(str(v), {"term_freq": 0})
                    t["term_freq"] += 1
                    continue
                raw_v = str(v)
                annot_spans: list = []
                if ft.type == "annotated_text":
                    from ..index.mappings import parse_annotated_text
                    raw_v, annot_spans = parse_annotated_text(raw_v)
                toks = list(svc.mappings.index_analyzer(ft).analyze(raw_v))
                for (cs, ce, anns) in annot_spans:
                    # annotation values occupy the first covered token's
                    # position/offsets, mirroring the index-time injection
                    tok0 = next((t for t in toks
                                 if cs <= t.start_offset < ce), None)
                    if tok0 is None:
                        continue
                    for a in anns:
                        toks.append(type(tok0)(
                            text=a, position=tok0.position,
                            start_offset=tok0.start_offset,
                            end_offset=tok0.end_offset))
                for tok in toks:
                    t = terms.setdefault(tok.text,
                                         {"term_freq": 0, "tokens": []})
                    t["term_freq"] += 1
                    entry = {}
                    if positions:
                        entry["position"] = tok.position
                    if offsets:
                        entry["start_offset"] = tok.start_offset
                        entry["end_offset"] = tok.end_offset
                    if entry:
                        t["tokens"].append(entry)
            if not terms:
                continue
            ndocs = max(sum(s.live_count for s in segs), 1)
            if term_statistics or tv_filter:
                for term, t in terms.items():
                    df, ttf = _stats(fname, term)
                    if term_statistics:
                        t["doc_freq"] = df
                        t["ttf"] = ttf
                    t["_df"] = df
            if tv_filter:
                import math
                min_tf = int(tv_filter.get("min_term_freq", 1))
                min_df = int(tv_filter.get("min_doc_freq", 1))
                max_df = int(tv_filter.get("max_doc_freq", 1 << 60))
                kept = {}
                for term, t in terms.items():
                    df = t["_df"]
                    if t["term_freq"] < min_tf or df < min_df or df > max_df:
                        continue
                    idf = math.log(1.0 + (ndocs - df + 0.5) / (df + 0.5))
                    kept[term] = (t["term_freq"] * idf, t)
                maxn = tv_filter.get("max_num_terms")
                ranked = sorted(kept.items(), key=lambda kv: -kv[1][0])
                if maxn is not None:
                    ranked = ranked[: int(maxn)]
                terms = {}
                for term, (score, t) in ranked:
                    t["score"] = round(score, 6)
                    terms[term] = t
            for t in terms.values():
                t.pop("_df", None)
            fblock: dict = {"terms": dict(sorted(terms.items()))}
            if field_statistics:
                sum_ttf = sum_df = 0
                for s in segs:
                    pb = s.postings.get(fname)
                    if pb is not None:
                        sum_df += len(pb.doc_ids)
                        sum_ttf += int(pb.tfs.sum())
                doc_count = 0
                for s in segs:
                    if fname in s.text_stats:
                        doc_count += s.text_stats[fname].doc_count
                    elif fname in s.postings:
                        import numpy as _np
                        doc_count += len(_np.unique(
                            s.postings[fname].doc_ids))
                fblock["field_statistics"] = {
                    "sum_doc_freq": sum_df, "doc_count": doc_count,
                    "sum_ttf": sum_ttf}
            out_fields[fname] = fblock
        return {"_index": svc.meta.name, "_id": resp_id, "found": found,
                "term_vectors": out_fields}

    def mtermvectors(self, body: dict, index: Optional[str] = None) -> dict:
        """Reference `action/termvectors/MultiTermVectorsRequest.java`."""
        docs = []
        for spec in body.get("docs", []):
            idx = spec.get("_index", index)
            if idx is None:
                raise ApiError(400, "action_request_validation_exception",
                               "mtermvectors doc needs an [_index]")
            docs.append(self.termvectors(
                idx, spec.get("_id"), body={k: v for k, v in spec.items()
                                            if not k.startswith("_")}))
        return {"docs": docs}

    # ---------------- reindex family ----------------

    def reindex(self, body: dict, refresh: bool = False) -> dict:
        src = body["source"]
        dest = body["dest"]
        query = {"query": src.get("query", {"match_all": {}}), "size": 10000}
        resp = self.search(src["index"], query)
        created = 0
        pipeline = dest.get("pipeline")
        for h in resp["hits"]["hits"]:
            self.index(dest["index"], h["_source"], id=h["_id"], pipeline=pipeline)
            created += 1
        if refresh and created:
            self.node.get_index(self.node.metadata.write_index(dest["index"])).refresh()
        return {"took": resp["took"], "created": created, "updated": 0,
                "total": created, "failures": []}

    def delete_by_query(self, index: str, body: dict, refresh: bool = False) -> dict:
        resp = self.search(index, {"query": body.get("query", {"match_all": {}}),
                                   "size": 10000})
        deleted = 0
        for h in resp["hits"]["hits"]:
            try:
                self.delete(h["_index"] or index, h["_id"])
                deleted += 1
            except ApiError:
                pass
        if refresh:
            for n in self.node.metadata.resolve(index):
                self.node.indices[n].refresh()
        return {"took": resp["took"], "deleted": deleted, "total": deleted,
                "failures": []}

    def update_by_query(self, index: str, body: Optional[dict] = None,
                        refresh: bool = False) -> dict:
        body = body or {}
        resp = self.search(index, {"query": body.get("query", {"match_all": {}}),
                                   "size": 10000})
        updated = 0
        script_body = body.get("script")
        for h in resp["hits"]["hits"]:
            new_src = h["_source"]
            if script_body is not None:
                new_src, op = _run_update_script_or_400(
                    script_body, new_src,
                    {"_index": h["_index"] or index, "_id": h["_id"]})
                if op == "none":
                    continue
                if op == "delete":
                    self.delete(h["_index"] or index, h["_id"])
                    updated += 1
                    continue
            self.index(h["_index"] or index, new_src, id=h["_id"])
            updated += 1
        if refresh:
            for n in self.node.metadata.resolve(index):
                self.node.indices[n].refresh()
        return {"took": resp["took"], "updated": updated, "total": updated,
                "failures": []}


def _search_snapshot(searchers: List[ShardSearcher], body: dict, index: str,
                     phase_hook=None, phase_ctx: Optional[dict] = None) -> dict:
    """Search against snapshotted segment lists (scroll/PIT)."""
    body = dict(body)
    body["_index_name"] = index
    from ..search.executor import _global_stats_contexts, reduce_shard_results
    stats = _global_stats_contexts(searchers)
    results = [s.query_phase(body, segments=s._snapshot_segments, shard_ord=i,
                             stats_ctx=stats[i])
               for i, s in enumerate(searchers)]
    if phase_hook is not None:
        phase_hook(results, body, phase_ctx if phase_ctx is not None else {})
    reduced = reduce_shard_results(results, body)
    by_shard: Dict[int, List] = {}
    for c in reduced["selected"]:
        by_shard.setdefault(c.shard, []).append(c)
    hits_by_key: Dict[tuple, dict] = {}
    for i, r in enumerate(results):
        sel = by_shard.get(r.shard, [])
        if sel:
            for c, h in zip(sel, searchers[i].fetch_phase(r, sel, body)):
                hits_by_key[(c.shard, c.seg_ord, c.local_doc)] = h
    hits = [hits_by_key[(c.shard, c.seg_ord, c.local_doc)]
            for c in reduced["selected"]
            if (c.shard, c.seg_ord, c.local_doc) in hits_by_key]
    resp = {"took": 0, "timed_out": False,
            "_shards": {"total": len(searchers), "successful": len(searchers),
                        "skipped": 0, "failed": 0},
            "hits": {"total": {"value": reduced["total"],
                               "relation": reduced.get("total_rel", "eq")},
                     "max_score": reduced["max_score"], "hits": hits}}
    if reduced["aggs"]:
        resp["aggregations"] = reduced["aggs"]
    return resp


def _deep_merge(base: dict, patch: dict) -> dict:
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _get_source_path(src: dict, path: str):
    node: Any = src
    for p in path.split("."):
        if isinstance(node, dict):
            node = node.get(p)
        else:
            return None
    return node


# =====================================================================
# namespaced sub-clients
# =====================================================================

class IndicesClient:
    def __init__(self, client: RestClient):
        self.c = client

    def create(self, index: str, body: Optional[dict] = None) -> dict:
        return _map_mapping_errors(self.c.node.create_index, index, body)

    def delete(self, index: str) -> dict:
        return _map_ds_errors(self.c.node.delete_index, index)

    def exists(self, index: str) -> bool:
        try:
            return bool(self.c.node.metadata.resolve(index, allow_no_indices=False))
        except IndexNotFoundError:
            return False

    def get(self, index: str) -> dict:
        out = {}
        for n in self.c.node.metadata.resolve(index, allow_no_indices=False):
            svc = self.c.node.indices[n]
            aliases = {a: am.indices[n] for a, am in self.c.node.metadata.aliases.items()
                       if n in am.indices}
            out[n] = {"settings": {"index": {**svc.meta.settings.get("index", {}),
                                             "number_of_shards": svc.meta.num_shards,
                                             "uuid": n}},
                      "mappings": svc.mappings.to_dict(),
                      "aliases": aliases}
        return out

    def get_mapping(self, index: str = "_all") -> dict:
        return {n: {"mappings": self.c.node.indices[n].mappings.to_dict()}
                for n in self.c.node.metadata.resolve(index)}

    def put_mapping(self, index: str, body: dict) -> dict:
        for n in self.c.node.metadata.resolve(index, allow_no_indices=False):
            svc = self.c.node.indices[n]
            # mapping merge mutates structures in-flight doc parses read
            with svc.write_lock:
                _map_mapping_errors(svc.mappings.merge, body)
                self.c.node._persist_meta(n)
        return {"acknowledged": True}

    def get_settings(self, index: str = "_all") -> dict:
        return {n: {"settings": {"index": self.c.node.indices[n].meta.settings.get("index", {})}}
                for n in self.c.node.metadata.resolve(index)}

    def put_settings(self, index: str, body: dict,
                     preserve_existing: bool = False) -> dict:
        """PUT /{index}/_settings (reference
        TransportUpdateSettingsAction): dynamic settings apply to open
        indices; static settings require the index to be closed; final
        settings never change."""
        return _map_admin_errors(
            self.c.node.update_index_settings, index, body,
            preserve_existing)

    def close(self, index: str) -> dict:
        """POST /{index}/_close (reference TransportCloseIndexAction)."""
        return _map_admin_errors(self.c.node.close_index, index)

    def open(self, index: str) -> dict:
        """POST /{index}/_open (reference TransportOpenIndexAction)."""
        return _map_admin_errors(self.c.node.open_index, index)

    def shrink(self, index: str, target: str,
               body: Optional[dict] = None) -> dict:
        """POST /{index}/_shrink/{target} (TransportResizeAction)."""
        return _map_admin_errors(self.c.node.resize_index, index, target,
                                 "shrink", body)

    def split(self, index: str, target: str,
              body: Optional[dict] = None) -> dict:
        return _map_admin_errors(self.c.node.resize_index, index, target,
                                 "split", body)

    def clone(self, index: str, target: str,
              body: Optional[dict] = None) -> dict:
        return _map_admin_errors(self.c.node.resize_index, index, target,
                                 "clone", body)

    def refresh(self, index: str = "_all") -> dict:
        for n in self.c.node.metadata.resolve(index):
            svc = self.c.node.indices[n]
            with svc.write_lock:
                svc.refresh()
        return {"_shards": {"successful": 1, "failed": 0}}

    def flush(self, index: str = "_all") -> dict:
        n_shards = 0
        for n in self.c.node.metadata.resolve(index):
            svc = self.c.node.indices[n]
            n_shards += len(svc.shards)
            with svc.write_lock:
                svc.flush()
        return {"_shards": {"successful": n_shards, "failed": 0}}

    def forcemerge(self, index: str = "_all", max_num_segments: int = 1) -> dict:
        for n in self.c.node.metadata.resolve(index):
            svc = self.c.node.indices[n]
            with svc.write_lock:
                svc.force_merge(max_num_segments)
        return {"_shards": {"successful": 1, "failed": 0}}

    def stats(self, index: str = "_all") -> dict:
        out = {n: self.c.node.indices[n].stats()
               for n in self.c.node.metadata.resolve(index)}
        total = {"docs": {"count": sum(v["docs"]["count"] for v in out.values())}}
        return {"_all": {"primaries": total, "total": total},
                "indices": {n: {"primaries": v, "total": v} for n, v in out.items()}}

    def analyze(self, index: Optional[str] = None, body: Optional[dict] = None) -> dict:
        body = body or {}
        text = body.get("text", "")
        texts = text if isinstance(text, list) else [text]
        if index is not None:
            svc = self.c.node.get_index(self.c.node.metadata.write_index(index))
            registry = svc.mappings.analysis
            if "field" in body:
                ft = svc.mappings.resolve_field(body["field"])
                analyzer = svc.mappings.index_analyzer(ft) if ft else registry.get("standard")
            else:
                analyzer = registry.get(body.get("analyzer", "standard"))
        else:
            from ..analysis import AnalysisRegistry
            analyzer = AnalysisRegistry().get(body.get("analyzer", "standard"))
        tokens = []
        for t in texts:
            for tok in analyzer.analyze(t):
                tokens.append({"token": tok.text, "position": tok.position,
                               "start_offset": tok.start_offset,
                               "end_offset": tok.end_offset, "type": "<ALPHANUM>"})
        return {"tokens": tokens}

    def get_alias(self, index: str = "_all", name: Optional[str] = None) -> dict:
        out: Dict[str, dict] = {}
        for a, am in self.c.node.metadata.aliases.items():
            if name and a != name:
                continue
            for n, cfg in am.indices.items():
                out.setdefault(n, {"aliases": {}})["aliases"][a] = cfg
        return out

    def update_aliases(self, body: dict) -> dict:
        return self.c.node.update_aliases(body.get("actions", []))

    def put_alias(self, index: str, name: str, body: Optional[dict] = None) -> dict:
        return self.c.node.update_aliases(
            [{"add": {"index": index, "alias": name, **(body or {})}}])

    def put_index_template(self, name: str, body: dict) -> dict:
        self.c.node.metadata.templates[name] = body
        return {"acknowledged": True}

    put_template = put_index_template

    def delete_index_template(self, name: str) -> dict:
        if self.c.node.metadata.templates.pop(name, None) is None:
            raise ApiError(404, "resource_not_found_exception",
                           f"index template [{name}] missing")
        return {"acknowledged": True}

    def exists_index_template(self, name: str) -> bool:
        return name in self.c.node.metadata.templates

    # -------- data streams (reference action/admin/indices/datastream) ----

    def create_data_stream(self, name: str) -> dict:
        from ..cluster import datastream as dstream
        return _map_ds_errors(dstream.create_data_stream, self.c.node, name)

    def get_data_stream(self, name: str = "*") -> dict:
        from ..cluster import datastream as dstream
        return {"data_streams": _map_ds_errors(dstream.get_data_streams,
                                               self.c.node, name)}

    def delete_data_stream(self, name: str) -> dict:
        from ..cluster import datastream as dstream
        return _map_ds_errors(dstream.delete_data_stream, self.c.node, name)


def _map_ds_errors(fn, *args):
    from ..cluster.datastream import DataStreamError
    try:
        return fn(*args)
    except DataStreamError as e:
        raise ApiError(400, "illegal_argument_exception", str(e))
    except IndexNotFoundError as e:
        raise ApiError(404, "index_not_found_exception", str(e))


class IngestClient:
    def __init__(self, client: RestClient):
        self.c = client

    def put_pipeline(self, id: str, body: dict) -> dict:
        self.c.node.ingest.put_pipeline(id, body)
        return {"acknowledged": True}

    def get_pipeline(self, id: Optional[str] = None) -> dict:
        svc = self.c.node.ingest
        if id:
            p = svc.get_pipeline(id)
            if p is None:
                raise ApiError(404, "resource_not_found_exception",
                               f"pipeline [{id}] not found")
            return {id: copy.deepcopy(p.config)}
        return {pid: copy.deepcopy(p.config)
                for pid, p in svc.pipelines.items()}

    def delete_pipeline(self, id: str) -> dict:
        self.c.node.ingest.delete_pipeline(id)
        return {"acknowledged": True}

    def simulate(self, body: dict) -> dict:
        return {"docs": self.c.node.ingest.simulate(body.get("pipeline", body),
                                                    body.get("docs", []))}


class SnapshotClient:
    def __init__(self, client: RestClient):
        self.c = client
        self.repos: Dict[str, dict] = {}

    def create_repository(self, repository: str, body: dict) -> dict:
        self.repos[repository] = body.get("settings", body)
        return {"acknowledged": True}

    def create(self, repository: str, snapshot: str, body: Optional[dict] = None,
               wait_for_completion: bool = True) -> dict:
        repo = self.repos.get(repository)
        if repo is None:
            raise ApiError(404, "repository_missing_exception",
                           f"[{repository}] missing")
        return self.c.node.snapshot(repo["location"], snapshot,
                                    (body or {}).get("indices", "_all"))

    def restore(self, repository: str, snapshot: str, body: Optional[dict] = None) -> dict:
        repo = self.repos.get(repository)
        if repo is None:
            raise ApiError(404, "repository_missing_exception",
                           f"[{repository}] missing")
        body = body or {}
        return self.c.node.restore(repo["location"], snapshot,
                                   body.get("rename_pattern"),
                                   body.get("rename_replacement"))

    def get(self, repository: str, snapshot: str = "_all") -> dict:
        import os
        repo = self.repos.get(repository)
        snaps = []
        if repo:
            seen = set()
            sdir = os.path.join(repo["location"], "snapshots")
            if os.path.isdir(sdir):
                for fn in sorted(os.listdir(sdir)):
                    if fn.endswith(".json"):
                        seen.add(fn[:-5])
            # legacy (pre-r4) directory-layout snapshots stay listed
            if os.path.isdir(repo["location"]):
                for d in sorted(os.listdir(repo["location"])):
                    if d in ("snapshots", "blobs"):
                        continue
                    if os.path.exists(os.path.join(repo["location"], d,
                                                   "manifest.json")):
                        seen.add(d)
            for name in sorted(seen):
                if snapshot in ("_all", "*") or name == snapshot:
                    snaps.append({"snapshot": name, "state": "SUCCESS"})
        return {"snapshots": snaps}


def _map_mapping_errors(fn, *args):
    """A mapping's date `format` this engine cannot read, or a vector
    field's space or method it does not have -> 400."""
    from ..index.date_formats import DateFormatError
    from ..index.mappings import VectorMappingError
    try:
        return fn(*args)
    except (DateFormatError, VectorMappingError) as e:
        raise ApiError(400, "mapper_parsing_exception", str(e))


def _map_admin_errors(fn, *args):
    """cluster/admin.py exceptions -> HTTP-shaped ApiErrors."""
    from ..cluster.admin import IndexClosedError, SettingsError
    try:
        return fn(*args)
    except IndexClosedError as e:
        raise ApiError(400, "index_closed_exception", str(e))
    except SettingsError as e:
        raise ApiError(400, "illegal_argument_exception", str(e))
    except IndexNotFoundError as e:
        raise ApiError(404, "index_not_found_exception", str(e))


class ClusterClient:
    def __init__(self, client: RestClient):
        self.c = client

    def put_settings(self, body: dict) -> dict:
        """PUT /_cluster/settings (reference
        TransportClusterUpdateSettingsAction): persistent/transient dynamic
        settings; null values reset."""
        return _map_admin_errors(self.c.node.update_cluster_settings, body)

    def get_settings(self, include_defaults: bool = False) -> dict:
        return self.c.node.get_cluster_settings()

    def health(self, index: Optional[str] = None) -> dict:
        node = self.c.node
        names = (node.metadata.resolve(index) if index
                 else list(node.indices.keys()))
        primaries = active = unassigned = 0
        status = "green"
        rank = {"green": 0, "yellow": 1, "red": 2}
        for n in names:
            svc = node.indices[n]
            for c in svc.table.copies:
                if c.state == "STARTED":
                    active += 1
                    if c.primary:
                        primaries += 1
                else:
                    unassigned += 1
            s = svc.health_status()
            if rank[s] > rank[status]:
                status = s
        total = active + unassigned
        return {"cluster_name": node.metadata.cluster_name, "status": status,
                "number_of_nodes": 1, "number_of_data_nodes": 1,
                "active_primary_shards": primaries, "active_shards": active,
                "relocating_shards": 0, "initializing_shards": 0,
                "unassigned_shards": unassigned,
                "active_shards_percent_as_number":
                    100.0 * active / total if total else 100.0}

    def state(self) -> dict:
        node = self.c.node
        return {"cluster_name": node.metadata.cluster_name,
                "version": node.metadata.version,
                "metadata": {"indices": {n: {"state": m.state,
                                             "settings": m.settings}
                                         for n, m in node.metadata.indices.items()}}}

    def stats(self) -> dict:
        return self.c.node.stats()


class CatClient:
    def __init__(self, client: RestClient):
        self.c = client

    def indices(self, format: str = "json") -> List[dict]:
        out = []
        for n, svc in sorted(self.c.node.indices.items()):
            st = svc.stats()
            buf = st["indexing"].get("buffer", {})
            out.append({"health": svc.health_status(), "status": "open",
                        "index": n,
                        "pri": str(svc.meta.num_shards),
                        "rep": str(svc.meta.num_replicas),
                        "docs.count": str(st["docs"]["count"]),
                        "store.size": str(st["store"]["size_in_bytes"]),
                        # write-pressure columns (ingest observatory):
                        # docs/bytes sitting in the writer buffer, merges
                        # run so far, merge groups still pending
                        "buffer.docs": str(buf.get("docs", 0)),
                        "buffer.bytes": str(buf.get("bytes", 0)),
                        "merges.total": str(st["merges"]["total"]),
                        "merges.backlog": str(st["merges"].get("backlog",
                                                               0))})
        return out

    def shards(self, index: str = "_all", format: str = "json") -> List[dict]:
        """_cat/shards: one row per shard copy with its device placement."""
        out = []
        node = self.c.node
        for n in sorted(node.metadata.resolve(index)):
            svc = node.indices[n]
            for c in sorted(svc.table.copies, key=lambda c: (c.shard, c.replica)):
                if c.primary:
                    docs = svc.shards[c.shard].num_docs
                else:
                    rep = svc.replicas.get((c.shard, c.replica))
                    docs = rep.num_docs if rep else 0
                out.append({"index": n, "shard": str(c.shard),
                            "prirep": "p" if c.primary else "r",
                            "state": c.state,
                            "docs": str(docs),
                            "node": (f"device-{c.device}"
                                     if c.device is not None else "")})
        return out

    def count(self, index: str = "_all") -> List[dict]:
        total = sum(self.c.node.indices[n].num_docs
                    for n in self.c.node.metadata.resolve(index))
        return [{"epoch": str(int(time.time())), "count": str(total)}]

    def thread_pool(self, format: str = "json") -> List[dict]:
        node = self.c.node
        return [{"node_name": node.node_name, "name": p["name"],
                 "size": str(p["size"]), "active": str(p["active"]),
                 "completed": str(p["completed"])}
                for p in node.thread_pools.stats()]

    def tasks(self, format: str = "json") -> List[dict]:
        return [{"action": t["action"], "task_id": str(t["id"]),
                 "running_time": str(t["running_time_in_nanos"]),
                 "cancellable": str(t["cancellable"]).lower()}
                for t in self.c.node.tasks.list()]

    def nodes(self, format: str = "json") -> List[dict]:
        stats = self.c.nodes_stats()["nodes"][self.c.node.node_name]
        return [{"name": self.c.node.node_name,
                 "node.role": "".join(r[0] for r in stats["roles"]),
                 "master": "*",
                 "segments.count": str(stats["indices"]["segments"]["count"]),
                 "docs.count": str(stats["indices"]["docs"]["count"])}]

    def health(self, format: str = "json") -> List[dict]:
        h = self.c.cluster.health()
        return [{"epoch": str(int(time.time())),
                 "cluster": h["cluster_name"], "status": h["status"],
                 "node.total": str(h["number_of_nodes"]),
                 "shards": str(h["active_shards"]),
                 "pri": str(h["active_primary_shards"]),
                 "unassign": str(h["unassigned_shards"])}]

    def segments(self, index: str = "_all",
                 format: str = "json") -> List[dict]:
        """_cat/segments with per-segment DEVICE residency from the HBM
        ledger: `memory.device` is the segment's total attributed HBM
        bytes, `memory.device.tenants` the per-kind breakdown (e.g.
        `aligned_postings=1048576,segment_columns=262144`)."""
        residency = self.c.node.hbm_ledger.segment_residency()
        out = []
        for n in sorted(self.c.node.metadata.resolve(index)):
            svc = self.c.node.indices[n]
            for si, sh in enumerate(svc.shards):
                for seg in sh.segments:
                    res = residency.get(getattr(seg, "uid", None)) \
                        or residency.get(seg.name) or {}
                    kinds = res.get("kinds", {})
                    out.append({"index": n, "shard": str(si),
                                "prirep": "p", "segment": seg.name,
                                "docs.count": str(seg.live_count),
                                "docs.deleted":
                                    str(seg.ndocs - seg.live_count),
                                "memory.device":
                                    str(res.get("total_bytes", 0)),
                                "memory.device.tenants": ",".join(
                                    f"{k}={v}" for k, v in
                                    sorted(kinds.items()))})
        return out

    def aliases(self, format: str = "json") -> List[dict]:
        out = []
        for alias, am in sorted(self.c.node.metadata.aliases.items()):
            for idx, cfg in sorted(am.indices.items()):
                out.append({"alias": alias, "index": idx,
                            "is_write_index":
                                str(cfg.get("is_write_index",
                                            False)).lower()})
        return out

    def templates(self, format: str = "json") -> List[dict]:
        return [{"name": name,
                 "index_patterns": str(t.get("index_patterns", [])),
                 "order": str(t.get("order", t.get("priority", 0)))}
                for name, t in sorted(
                    self.c.node.metadata.templates.items())]

    def allocation(self, format: str = "json") -> List[dict]:
        shards = sum(len(svc.shards) for svc in self.c.node.indices.values())
        return [{"node": self.c.node.node_name, "shards": str(shards)}]
