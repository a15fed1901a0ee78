"""The Node: owns indices (each = N shard engines + searchers), the ingest
service, caches, and breakers. Analog of reference `node/Node.java` +
`indices/IndicesService.java` + `index/IndexService.java`.

Shard layout is device-aware: with a `jax.sharding.Mesh` available, each
shard's segments are placed on the mesh device for its shard slot
(parallel/placement.py); on one chip all shards share it (still giving the
reference's concurrency-by-shard semantics for the API surface)."""

from __future__ import annotations

import os
import shutil
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..analysis import AnalysisRegistry
from ..index.engine import Engine
from ..index.mappings import Mappings
from ..ingest import IngestService
from ..search import impactpath
from ..search.executor import ShardSearcher, msearch_batched, search_shards
from ..utils.breaker import BreakerService
from ..obs import flight_recorder as _fr
from ..obs import ingest_obs as _iobs
from ..utils.metrics import METRICS
from ..utils.slowlog import SlowLog
from ..utils.tasks import TaskRegistry
from ..utils.threadpool import ThreadPools
from .routing import shard_for
from .state import (ClusterMetadata, ClusterStateError, IndexMetadata,
                    IndexNotFoundError, ResourceAlreadyExistsError, AliasMetadata)


class IndexService:
    def __init__(self, meta: IndexMetadata, mapping: Optional[dict],
                 data_path: Optional[str] = None, thread_pools=None):
        self.meta = meta
        # remote-backed storage mirror (index/remote.py), attached by the
        # Node when a remote root is configured
        self.remote = None
        analysis = AnalysisRegistry(meta.settings.get("index", {}).get("analysis",
                                    meta.settings.get("analysis")))
        self.mappings = Mappings(mapping, analysis=analysis,
                                 dynamic=(mapping or {}).get("dynamic", True))
        sim_settings = meta.settings.get("index", {}).get("similarity",
                       meta.settings.get("similarity", {}))
        self.default_sim = sim_settings.get("default") if isinstance(sim_settings, dict) else None
        self.shards: List[Engine] = []
        self.searchers: List[ShardSearcher] = []
        for sid in range(meta.num_shards):
            path = os.path.join(data_path, meta.name, str(sid)) if data_path else None
            eng = Engine(self.mappings, path=path)
            eng.index_name = meta.name   # labels per-index write-path obs
            self.shards.append(eng)
            self.searchers.append(ShardSearcher(eng, shard_id=sid,
                                                similarity=self.default_sim,
                                                index_key=meta.name))
        self.generation = 0  # bumped on refresh/writes: request-cache key part
        # per-index write serialization (the analog of the reference's
        # per-shard engine write locks, InternalEngine.java:1): acquired by
        # the client layer AFTER alias/pipeline resolution, so every
        # transport (dict API, HTTP, dist) serializes mutations of this
        # index while writes to other indices proceed in parallel
        self.write_lock = threading.RLock()
        self.thread_pools = thread_pools
        self.search_slowlog = SlowLog(meta.name, meta.settings, "search",
                                      "query")
        self.index_slowlog = SlowLog(meta.name, meta.settings, "indexing",
                                     "index")
        self._init_replicas()

    def _init_replicas(self) -> None:
        """Allocate shard copies over devices and build replica shards
        (segment replication: replicas re-host the primary's immutable
        segments on their own device — cluster/replication.py)."""
        import jax

        from ..parallel.placement import ShardAllocator
        from .replication import ReplicaShard

        devices = jax.devices()
        self.allocator = ShardAllocator(len(devices))
        self.table = self.allocator.allocate(self.meta.num_shards,
                                             self.meta.num_replicas)
        self.replicas: Dict[Tuple[int, int], ReplicaShard] = {}
        self.replica_searchers: Dict[Tuple[int, int], ShardSearcher] = {}
        self._devices = devices
        for copy in self.table.copies:
            if copy.primary or copy.device is None:
                continue
            self._build_replica(copy)
        self._rr = 0

    def _build_replica(self, copy) -> None:
        from .replication import ReplicaShard

        dev = self._devices[copy.device]
        rep = ReplicaShard(self.shards[copy.shard], copy.shard,
                           copy.replica, device=dev)
        rep.sync(warm=False)  # adopt recovered/restored segments now
        self.replicas[(copy.shard, copy.replica)] = rep
        s = ShardSearcher(self.shards[copy.shard], shard_id=copy.shard,
                          similarity=self.default_sim,
                          index_key=self.meta.name, device=dev)
        s.replica = rep
        self.replica_searchers[(copy.shard, copy.replica)] = s

    def fail_device(self, device_ord: int) -> None:
        """Device (chip) failure: re-allocate its shard copies and rebuild
        the moved replicas on their new devices; a lost primary promotes a
        surviving replica first (reference allocation + promotion flow)."""
        lost_primaries = [c.shard for c in self.table.copies
                          if c.primary and c.device == device_ord]
        for sid in lost_primaries:
            try:
                self.fail_primary(sid)
            except ClusterStateError:
                # no replica to promote: the shard goes unassigned and the
                # index reports red (reference allocation on primary loss)
                pcopy = next(c for c in self.table.for_shard(sid)
                             if c.primary)
                pcopy.device = None
                pcopy.state = "UNASSIGNED"
        changed = self.allocator.fail_device(device_ord, self.table)
        for copy in changed:
            key = (copy.shard, copy.replica)
            self.replicas.pop(key, None)
            self.replica_searchers.pop(key, None)
            if not copy.primary and copy.device is not None:
                self._build_replica(copy)
        self.generation += 1

    def route(self, doc_id: str, routing: Optional[str] = None) -> Engine:
        return self.shards[shard_for(routing or doc_id, self.meta.num_shards)]

    def search_copies(self) -> List[ShardSearcher]:
        """One searcher per shard, round-robin across started copies
        (reference OperationRouting preference=round-robin replica fan-out)."""
        self._rr += 1
        out = []
        for sid in range(self.meta.num_shards):
            copies = [c for c in self.table.for_shard(sid)
                      if c.state == "STARTED"]
            if not copies:
                continue  # shard lost entirely -> partial results (red)
            pick = copies[self._rr % len(copies)]
            if pick.primary:
                out.append(self.searchers[sid])
            else:
                out.append(self.replica_searchers[(sid, pick.replica)])
        return out

    def fail_primary(self, shard_id: int) -> None:
        """Simulate primary loss: promote a started replica (segments it has
        already synced) and rebuild its searcher. Raises if no replica."""
        from .replication import promote_to_primary

        cand = [(k, r) for k, r in self.replicas.items()
                if k[0] == shard_id and r.state == "STARTED"]
        if not cand:
            raise ClusterStateError(
                f"no started replica to promote for shard [{shard_id}]")
        (key, rep) = cand[0]
        new_primary = promote_to_primary(self.mappings, rep,
                                         self.shards[shard_id].primary_term + 1)
        self.shards[shard_id] = new_primary
        self.searchers[shard_id] = ShardSearcher(
            new_primary, shard_id=shard_id, similarity=self.default_sim,
            index_key=self.meta.name, device=rep.device)
        # the promoted copy takes over the primary slot in the table;
        # remaining replicas track the new primary
        del self.replicas[key]
        del self.replica_searchers[key]
        pcopy = next(c for c in self.table.for_shard(shard_id) if c.primary)
        rcopy = next(c for c in self.table.for_shard(shard_id)
                     if c.replica == key[1])
        pcopy.device = rcopy.device
        pcopy.state = "STARTED"
        self.table.copies.remove(rcopy)
        for (sid, rid), r in self.replicas.items():
            if sid == shard_id:
                r.primary = new_primary
                r.sync()
                self.replica_searchers[(sid, rid)].engine = new_primary
        self.generation += 1

    def health_status(self) -> str:
        if any(c.state != "STARTED" and c.primary for c in self.table.copies):
            return "red"
        if any(c.state != "STARTED" for c in self.table.copies):
            return "yellow"
        return "green"

    def refresh(self) -> None:
        for s in self.shards:
            s.refresh()
        if self.replicas:
            t0 = time.perf_counter()
            for rep in self.replicas.values():
                rep.sync()
            if _iobs.enabled():
                _iobs.record_replica_sync(
                    len(self.replicas), (time.perf_counter() - t0) * 1000.0)
        self.generation += 1

    def flush(self) -> None:
        # persistence is IO-bound: fan shards out on the write pool when the
        # node provides one (reference ThreadPool.Names.FLUSH)
        if self.thread_pools is not None and len(self.shards) > 1:
            self.thread_pools.run_blocking("write",
                                           [s.flush for s in self.shards])
        else:
            for s in self.shards:
                s.flush()
        self.generation += 1
        # remote-backed storage: mirror every shard's new commit (reference
        # RemoteStoreRefreshListener uploads after each refresh/commit).
        # An upload failure must NOT fail the LOCAL commit — the shard
        # keeps serving, the tracker records the failure and the lag, and
        # the next flush retries (reference marks the shard lagging)
        if self.remote is not None:
            for sid, eng in enumerate(self.shards):
                if eng.path:
                    try:
                        self.remote.upload_shard(eng.path, sid)
                    except Exception:   # noqa: BLE001
                        # failure + lag recorded by the tracker; also
                        # counted into the write-path failure family
                        _iobs.count("indexing.flush.remote_failed")
            try:
                self.remote.upload_index_meta({
                    "settings": self.meta.settings,
                    "mappings": self.mappings.to_dict(),
                    "state": self.meta.state})
            except Exception:           # noqa: BLE001
                # counted by upload_index_meta itself, mirrored here so
                # `indexing.flush.remote_failed` covers every swallow
                _iobs.count("indexing.flush.remote_failed")

    def force_merge(self, max_num_segments: int = 1) -> None:
        for s in self.shards:
            s.force_merge(max_num_segments)
        # merged segments replace the shared objects; replicas must adopt
        # them or deletes against the merged set stay invisible on copies
        for rep in self.replicas.values():
            rep.sync()
        self.generation += 1

    @property
    def num_docs(self) -> int:
        return sum(s.num_docs for s in self.shards)

    def stats(self) -> dict:
        seg_count = sum(len(s.segments) for s in self.shards)
        store_bytes = 0
        for sh in self.shards:
            for seg in sh.segments:
                for pb in seg.postings.values():
                    store_bytes += pb.doc_ids.nbytes + pb.tfs.nbytes + pb.starts.nbytes
                for col in seg.numeric_cols.values():
                    store_bytes += col.values.nbytes
        ops = {k: sum(s.stats[k] for s in self.shards)
               for k in ("index_ops", "delete_ops", "refreshes", "flushes", "merges")}
        buf = [s.buffer_stats() for s in self.shards]
        # per-index refresh-to-visible percentiles: the accept→searchable
        # sketch this index's refreshes recorded ({} until the first one)
        rtv = METRICS.percentiles(
            f"indexing.index.{self.meta.name}.refresh_to_visible_ms")
        return {"docs": {"count": self.num_docs},
                "store": {"size_in_bytes": store_bytes},
                "slowlog": {"search": self.search_slowlog.stats(),
                            "indexing": self.index_slowlog.stats()},
                "segments": {"count": seg_count},
                "indexing": {"index_total": ops["index_ops"],
                             "delete_total": ops["delete_ops"],
                             "buffer": {
                                 "docs": sum(b["docs"] for b in buf),
                                 "bytes": sum(b["bytes"] for b in buf)}},
                "refresh": {"total": ops["refreshes"],
                            **({"refresh_to_visible_ms": rtv}
                               if rtv else {})},
                "flush": {"total": ops["flushes"]},
                "merges": {"total": ops["merges"],
                           "backlog": sum(s.merge_backlog()
                                          for s in self.shards)},
                **({"remote_store": self.remote.stats()}
                   if self.remote is not None else {})}

    def close(self) -> None:
        for s in self.shards:
            s.close()


class RequestCache:
    """Shard-request cache (reference IndicesRequestCache): response fragments
    keyed by (index, request-json, index generation); invalidated by writes
    via the generation."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._store: Dict[tuple, dict] = {}
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple) -> Optional[dict]:
        v = self._store.get(key)
        if v is None:
            self.misses += 1
        else:
            self.hits += 1
        return v

    def put(self, key: tuple, value: dict) -> None:
        if len(self._store) >= self.max_entries:
            try:
                # concurrent putters can race the same eviction victim
                # (32-thread closed loops hit this): the loser's pop must
                # not raise out of the search path
                self._store.pop(next(iter(self._store)), None)
            except (StopIteration, RuntimeError):
                pass  # store emptied/resized underfoot — nothing to evict
        self._store[key] = value

    def stats(self) -> dict:
        return {"hit_count": self.hits, "miss_count": self.misses,
                "entries": len(self._store)}


class Node:
    def __init__(self, data_path: Optional[str] = None,
                 cluster_name: str = "opensearch-tpu", node_name: str = "node-0",
                 mesh_service=None, remote_root: Optional[str] = None):
        self.metadata = ClusterMetadata(cluster_name)
        self.node_name = node_name
        self.data_path = data_path
        # remote-backed storage root (reference remote store repository):
        # when set, every flush mirrors shard commits to this blob root and
        # recovery can restore an index from the mirror alone
        self.remote_root = (remote_root
                            or os.environ.get("OPENSEARCH_TPU_REMOTE_ROOT")
                            or None)
        self.remote_stores: Dict[str, object] = {}
        self.indices: Dict[str, IndexService] = {}
        # cluster-metadata mutations (index create/delete/open/close,
        # template changes) serialize here — the single-master analog of
        # the reference's cluster-state update task queue
        self.meta_lock = threading.RLock()
        self.ingest = IngestService()
        from ..search.pipeline import SearchPipelineService
        self.search_pipelines = SearchPipelineService()
        self.breakers = BreakerService()
        self.request_cache = RequestCache()
        self.tasks = TaskRegistry()
        from ..utils.backpressure import SearchBackpressureService
        self.search_backpressure = SearchBackpressureService()
        self.thread_pools = ThreadPools()
        from ..utils.wlm import WorkloadManagement
        from .lifecycle import LifecycleService
        self.wlm = WorkloadManagement()
        self.lifecycle = LifecycleService(self)
        from ..utils.trace import TRACER
        self.tracer = TRACER
        # flight recorder (obs/flight_recorder.py): per-request black-box
        # event journal + anomaly dumps; process singleton like TRACER
        self.flight_recorder = _fr.RECORDER
        from .failure import FailureDetector
        self.failure_detector = FailureDetector(self)
        # node-level op counters (reference NodeIndicesStats rollup)
        self.op_counters = {"search_total": 0, "search_time_ms": 0.0,
                            "get_total": 0, "index_total": 0,
                            "index_time_ms": 0.0}
        # SPMD mesh dispatch (parallel/service.py): pass a MeshSearchService
        # the SPMD mesh path is ON BY DEFAULT whenever more than one device
        # is visible (a pod slice, or the virtual 8-CPU-device test mesh);
        # OPENSEARCH_TPU_MESH=0 disables it, =1 forces it even single-chip.
        # Eligible searches run the distributed program; everything else
        # falls back to the host shard loop with identical results.
        # mesh_service=False pins the TRUE host loop (parity-test
        # reference clients must not silently auto-enable a mesh)
        if mesh_service is False:
            mesh_service = None
        elif mesh_service is None:
            flag = os.environ.get("OPENSEARCH_TPU_MESH")
            enable = (flag not in (None, "", "0") if flag is not None
                      else self._device_count() > 1)
            if enable:
                from ..parallel.service import MeshSearchService
                mesh_service = MeshSearchService()
        self.mesh_service = mesh_service
        # cross-cluster search (reference RemoteClusterService): registered
        # peer Nodes searchable via "alias:index" expressions. Peers are
        # in-process, so CCS fans their shard searchers into THIS
        # coordinator's single reduce — full-fidelity aggs and unified DFS
        # stats across clusters (ccs_minimize_roundtrips=false model)
        self.remote_clusters: Dict[str, "Node"] = {}
        # HBM ledger (obs/hbm_ledger.py): the single source of truth for
        # device memory. Every residency tenant — fastpath aligned
        # postings, segment column pytrees, partial-residency arrays,
        # filter-specialized copies, nested sort columns — registers an
        # attributed allocation there, and the fielddata-breaker charge
        # is DERIVED from the registration (oslint OSL506: the ledger is
        # the sole charge path). Process singleton, matching the
        # one-device-per-process reality.
        from ..obs.hbm_ledger import LEDGER
        self.hbm_ledger = LEDGER
        LEDGER.set_breaker(self.breakers.breaker("fielddata"))
        # serving scheduler (serving/scheduler.py): coalesces concurrent
        # eligible searches into one batched device program invocation.
        # On by default whenever the mesh is attached; OPENSEARCH_TPU_SCHED
        # forces it on (single-chip kernel batching) or off
        from ..serving import ServingScheduler
        self.serving = ServingScheduler(self)
        # fleet observability (obs/timeseries.py + obs/slo.py): the
        # time-series retention ring behind `_nodes/stats/history` and
        # the SLO burn-rate engine behind `GET /_slo`. Process singletons
        # like METRICS/RECORDER/LEDGER; the sampler thread does NOT
        # auto-start (tests tick deterministically; a server calls
        # `SAMPLER.ensure_started()`)
        from ..obs.slo import SLO_ENGINE
        from ..obs.timeseries import SAMPLER
        self.timeseries = SAMPLER
        self.slo = SLO_ENGINE
        # query insights (obs/insights.py): workload fingerprinting +
        # heavy-hitter attribution at the search boundary — the input
        # the SLO-burn → remediation loop attributes blame with.
        # Process singleton like METRICS/RECORDER/SAMPLER.
        from ..obs.insights import INSIGHTS
        self.insights = INSIGHTS
        # remediation actuator (serving/remediator.py): the closed loop
        # from a firing slo.burn alert to bounded admission-level action
        # (shed offending shapes, tighten admission, deprioritize a sick
        # member). Process singleton, DISARMED by default — the serving
        # hot path pays one attribute read; a server, the traffic harness
        # and tests arm it explicitly (`arm(node=...)`)
        from ..serving.remediator import REMEDIATOR
        self.remediation = REMEDIATOR
        # persistent tasks (reference persistent/AllocatedPersistentTask):
        # durable task table + resumable executors; built-in: reindex
        from ..utils.persistent_tasks import PersistentTasksService
        self.persistent_tasks = PersistentTasksService(data_path,
                                                       self.thread_pools)
        self.persistent_tasks.register_executor("reindex",
                                                self._persistent_reindex)
        self.start_time = time.time()          # wall clock, display only
        self._start_mono = time.monotonic()    # durations (uptime)
        if data_path:
            os.makedirs(data_path, exist_ok=True)
            self._recover_indices()
            self._recover_data_streams()
            self.persistent_tasks.resume_all()

    @staticmethod
    def _device_count() -> int:
        import jax
        return len(jax.devices())

    # ---------------- index lifecycle ----------------

    def create_index(self, name: str, body: Optional[dict] = None) -> dict:
        with self.meta_lock:
            return self._create_index_locked(name, body)

    def _create_index_locked(self, name: str,
                             body: Optional[dict] = None) -> dict:
        if name in self.indices:
            raise ResourceAlreadyExistsError(f"index [{name}] already exists")
        body = body or {}
        settings = dict(body.get("settings", {}))
        mapping = body.get("mappings")
        # apply matching index templates (reference MetadataIndexTemplateService)
        for tmpl in reversed(self.metadata.matching_templates(name)):
            tbody = tmpl.get("template", tmpl)
            tsettings = tbody.get("settings", {})
            merged = dict(tsettings)
            merged.update(settings)
            settings = merged
            if mapping is None and tbody.get("mappings"):
                mapping = tbody["mappings"]
        meta = IndexMetadata(name, settings={"index": settings.get("index", settings)})
        svc = IndexService(meta, mapping, self.data_path,
                           thread_pools=self.thread_pools)
        self.indices[name] = svc
        self.metadata.indices[name] = meta
        self._attach_remote(name)
        for alias, acfg in body.get("aliases", {}).items():
            self._put_alias(alias, name, acfg)
        self.metadata.bump()
        self._persist_meta(name)
        return {"acknowledged": True, "shards_acknowledged": True, "index": name}

    def delete_index(self, expression: str, _ds_guard: bool = True) -> dict:
        from .datastream import (DataStreamError, guard_backing_delete,
                                 is_backing, release_deleted)
        if _ds_guard and expression in self.metadata.data_streams:
            # reference rejects index-API deletes of a data stream
            raise DataStreamError(
                f"[{expression}] is a data stream; use the data stream "
                f"delete API")
        names = self.metadata.resolve(expression, allow_no_indices=False)
        is_wild = "*" in str(expression) or "?" in str(expression)
        if _ds_guard:
            if is_wild:
                # wildcards skip (hidden) backing indices, like the
                # reference's expand-wildcards handling
                names = [n for n in names if is_backing(self, n) is None]
                if not names:
                    return {"acknowledged": True}
            else:
                for name in names:
                    guard_backing_delete(self, name)
        else:
            # guard-exempt path (ILM delete): never remove a write index
            for name in names:
                ds_name = is_backing(self, name)
                if ds_name is not None and \
                        self.metadata.data_streams[ds_name].write_index == name:
                    raise DataStreamError(
                        f"cannot delete the write index [{name}] of data "
                        f"stream [{ds_name}]")
        for name in names:
            with self.meta_lock:
                svc = self.indices.pop(name, None)
                self.metadata.indices.pop(name, None)
                for am in self.metadata.aliases.values():
                    am.indices.pop(name, None)
            if svc:
                # drain in-flight writers before tearing the engine down
                with svc.write_lock:
                    svc.close()
            if self.data_path:
                p = os.path.join(self.data_path, name)
                if os.path.exists(p):
                    shutil.rmtree(p)
            # a deleted index must not resurrect from the remote mirror on
            # the next restart, and a re-created index must not inherit a
            # stale mirror generation
            self.remote_stores.pop(name, None)
            if self.remote_root:
                rp = os.path.join(self.remote_root, name)
                if os.path.exists(rp):
                    shutil.rmtree(rp, ignore_errors=True)
        self.metadata.aliases = {a: am for a, am in self.metadata.aliases.items()
                                 if am.indices}
        if not _ds_guard:
            release_deleted(self, names)
        self.metadata.bump()
        return {"acknowledged": True}

    def get_index(self, name: str) -> IndexService:
        if name not in self.indices:
            raise IndexNotFoundError(f"no such index [{name}]")
        return self.indices[name]

    def index_service_for_write(self, name: str, auto_create: bool = True) -> IndexService:
        try:
            concrete = self.metadata.write_index(name)
        except IndexNotFoundError:
            if not auto_create:
                raise
            with self.meta_lock:
                # re-check under the lock: another writer (or an alias/
                # data-stream creation) may have claimed the name while
                # we waited — re-resolve rather than assume the concrete
                # index equals the request name
                try:
                    concrete = self.metadata.write_index(name)
                except IndexNotFoundError:
                    self._create_index_locked(name)
                    concrete = self.metadata.write_index(name)
        svc = self.indices[concrete]
        if svc.meta.state == "close":
            from .admin import IndexClosedError
            raise IndexClosedError(f"closed index [{concrete}]")
        return svc

    # ---------------- aliases ----------------

    def _put_alias(self, alias: str, index: str, cfg: Optional[dict] = None) -> None:
        am = self.metadata.aliases.setdefault(alias, AliasMetadata(alias))
        am.indices[index] = cfg or {}

    def update_aliases(self, actions: List[dict]) -> dict:
        for action in actions:
            ((verb, spec),) = action.items()
            indices = spec.get("indices", [spec.get("index")])
            aliases = spec.get("aliases", [spec.get("alias")])
            for idx in indices:
                for name in self.metadata.resolve(idx, allow_no_indices=False):
                    for al in aliases:
                        if verb == "add":
                            cfg = {k: v for k, v in spec.items()
                                   if k in ("filter", "is_write_index", "routing")}
                            self._put_alias(al, name, cfg)
                        elif verb == "remove":
                            am = self.metadata.aliases.get(al)
                            if am:
                                am.indices.pop(name, None)
                        else:
                            raise ClusterStateError(f"unknown alias action [{verb}]")
        self.metadata.aliases = {a: am for a, am in self.metadata.aliases.items()
                                 if am.indices}
        self.metadata.bump()
        return {"acknowledged": True}

    # ---------------- persistence / recovery ----------------

    def _persist_meta(self, name: str) -> None:
        if not self.data_path:
            return
        import json
        svc = self.indices[name]
        p = os.path.join(self.data_path, name)
        os.makedirs(p, exist_ok=True)
        with open(os.path.join(p, "index_meta.json"), "w") as fh:
            json.dump({"settings": svc.meta.settings,
                       "mappings": svc.mappings.to_dict(),
                       "state": svc.meta.state}, fh)

    # -------- index admin (cluster/admin.py; reference transport actions
    # under action/admin/indices/{settings,close,open,shrink}) --------

    def update_index_settings(self, expression: str, body: dict,
                              preserve_existing: bool = False) -> dict:
        from . import admin
        return admin.update_index_settings(self, expression, body,
                                           preserve_existing)

    def close_index(self, expression: str) -> dict:
        from . import admin
        return admin.close_index(self, expression)

    def open_index(self, expression: str) -> dict:
        from . import admin
        return admin.open_index(self, expression)

    def resize_index(self, source: str, target: str, kind: str,
                     body: Optional[dict] = None) -> dict:
        from . import admin
        return admin.resize_index(self, source, target, kind, body)

    def update_cluster_settings(self, body: dict) -> dict:
        from . import admin
        return admin.update_cluster_settings(self, body)

    def get_cluster_settings(self) -> dict:
        from . import admin
        return admin.get_cluster_settings(self)

    # -------- data streams (cluster/datastream.py) --------

    def _persist_data_streams(self) -> None:
        if not self.data_path:
            return
        import json
        with open(os.path.join(self.data_path, "data_streams.json"),
                  "w") as fh:
            json.dump({n: {"generation": ds.generation,
                           "indices": ds.indices}
                       for n, ds in self.metadata.data_streams.items()}, fh)

    def _recover_data_streams(self) -> None:
        import json

        from .datastream import DataStreamMetadata
        p = os.path.join(self.data_path, "data_streams.json")
        if not os.path.exists(p):
            return
        with open(p) as fh:
            saved = json.load(fh)
        for name, d in saved.items():
            indices = [i for i in d["indices"] if i in self.indices]
            if not indices:
                continue     # every backing index lost: the stream is gone
            self.metadata.data_streams[name] = DataStreamMetadata(
                name=name, generation=d["generation"], indices=indices)

    def resolve_open(self, expression, allow_no_indices: bool = True):
        """resolve() then drop closed indices from wildcard expansions;
        explicitly named closed indices raise IndexClosedError."""
        from . import admin
        names = self.metadata.resolve(expression, allow_no_indices)
        return admin.check_open(self, names, expression)

    def _reopen_service(self, name: str) -> None:
        """Re-apply statically-configurable settings after _open (analysis
        chain, default similarity) without rebuilding the engines."""
        from ..analysis import AnalysisRegistry
        svc = self.indices[name]
        idx = svc.meta.settings.get("index", {})
        svc.mappings.analysis = AnalysisRegistry(
            idx.get("analysis", svc.meta.settings.get("analysis")))
        # re-register programmatic chains (search_as_you_type shingle/prefix
        # analyzers live in the registry, not the user's settings)
        for ft in svc.mappings.fields.values():
            if ft.type == "search_as_you_type":
                shingles = sum(1 for s in ft.subfields if s.endswith("gram"))
                svc.mappings.analysis.ensure_sayt_chains(shingles + 1)
        sim = idx.get("similarity", svc.meta.settings.get("similarity", {}))
        svc.default_sim = (sim.get("default")
                           if isinstance(sim, dict) else None)
        for s in svc.searchers:
            s.similarity = svc.default_sim
        svc.generation += 1
        self._persist_meta(name)

    def _recover_indices(self) -> None:
        import json
        for name in sorted(os.listdir(self.data_path)):
            meta_path = os.path.join(self.data_path, name, "index_meta.json")
            if not os.path.exists(meta_path):
                continue
            with open(meta_path) as fh:
                saved = json.load(fh)
            meta = IndexMetadata(name, settings=saved.get("settings", {}))
            meta.state = saved.get("state", "open")
            svc = IndexService(meta, saved.get("mappings"), self.data_path,
                               thread_pools=self.thread_pools)
            self.indices[name] = svc
            self.metadata.indices[name] = meta
            self._attach_remote(name)
        # remote-backed indices absent locally (lost data dir, fresh node):
        # restore from the mirror alone — the headline remote-store promise
        # (reference RestoreRemoteStoreAction)
        from ..index.remote import remote_indices
        for name in remote_indices(self.remote_root):
            if name not in self.indices:
                self.restore_from_remote(name)

    # -------- remote-backed storage (index/remote.py) --------

    def _attach_remote(self, name: str) -> None:
        """Give an index its remote mirror when the node has a remote root
        and the index doesn't opt out (index.remote_store.enabled=false)."""
        if not self.remote_root:
            return
        svc = self.indices[name]
        rs_cfg = svc.meta.settings.get("index", {}).get("remote_store", {})
        if isinstance(rs_cfg, dict) and str(rs_cfg.get("enabled", True)) \
                in ("False", "false", "0"):
            return
        from ..index.remote import RemoteSegmentStore
        store = self.remote_stores.get(name)
        if store is None:
            store = RemoteSegmentStore(self.remote_root, name)
            self.remote_stores[name] = store
        svc.remote = store

    def restore_from_remote(self, name: str) -> dict:
        """Materialize an index from its remote mirror: download the latest
        generation of every shard into the local data dir, then recover the
        engines from the restored commit points + segments."""
        from ..index.remote import RemoteSegmentStore
        if not self.remote_root:
            raise ClusterStateError("no remote store root configured")
        if name in self.indices:
            raise ResourceAlreadyExistsError(
                f"index [{name}] exists; close and delete it before a "
                f"remote restore")
        if not self.data_path:
            raise ClusterStateError("remote restore requires a node data_path")
        store = RemoteSegmentStore(self.remote_root, name)
        saved = store.load_index_meta()
        if saved is None:
            raise IndexNotFoundError(f"no remote index [{name}]")
        restored_files = 0
        for sid in store.shard_ids():
            dest = os.path.join(self.data_path, name, str(sid))
            restored_files += store.restore_shard(sid, dest)
        meta = IndexMetadata(name, settings=saved.get("settings", {}))
        meta.state = saved.get("state", "open")
        svc = IndexService(meta, saved.get("mappings"), self.data_path,
                           thread_pools=self.thread_pools)
        self.indices[name] = svc
        self.metadata.indices[name] = meta
        self.remote_stores[name] = store
        svc.remote = store
        self._persist_meta(name)
        self.metadata.bump()
        return {"index": name, "restored_files": restored_files,
                "shards": len(store.shard_ids())}

    # -------- persistent-task executors (persistent/ reference) --------

    def _persistent_reindex(self, params: dict, progress: dict,
                            checkpoint) -> dict:
        """Resumable reindex: copies live docs of `source` into `dest` in
        _id order, checkpointing the done-count per batch — a restart
        resumes from the last checkpoint instead of starting over
        (reference reindex runs as a persistent task for exactly this)."""
        src = params["source"]
        dest = params["dest"]
        batch = int(params.get("batch", 500))
        if src not in self.indices:
            raise IndexNotFoundError(f"no such index [{src}]")
        svc = self.indices[src]
        # collect (id, segment ref, local) ONLY — sources are fetched per
        # batch at write time, so memory stays O(ids), not O(corpus)
        # (the reference streams scroll batches for the same reason)
        refs = []
        for sh in svc.shards:
            for seg in sh.segments:
                for local, did in enumerate(seg.ids):
                    if seg.live[local]:
                        refs.append((did, seg, local))
        refs.sort(key=lambda t: t[0])
        done = int(progress.get("docs", 0))
        dsvc = self.index_service_for_write(dest)
        while done < len(refs):
            for did, seg, local in refs[done: done + batch]:
                dsvc.route(did, None).index_doc(did,
                                                dict(seg.sources[local]))
            done = min(done + batch, len(refs))
            checkpoint({"docs": done, "total": len(refs)})
        dsvc.refresh()
        dsvc.generation += 1
        return {"docs": done, "total": len(refs)}

    # ---------------- snapshots (reference snapshots/SnapshotsService +
    # repositories/blobstore/BlobStoreRepository.java: incremental shard
    # snapshots with per-file dedup) ----------------

    def snapshot(self, repo_path: str, snapshot_name: str,
                 indices: str = "_all") -> dict:
        """Incremental, content-addressed snapshot: every file is stored
        once per repository under blobs/<md5>; a snapshot is a manifest
        mapping file paths to blob digests. Repeat snapshots of unchanged
        indices copy ZERO segment bytes (segments are immutable), exactly
        the reference's incremental shard-snapshot behavior."""
        import json

        from ..index.remote import _md5
        names = self.metadata.resolve(indices)
        snaps_dir = os.path.join(repo_path, "snapshots")
        blob_dir = os.path.join(repo_path, "blobs")
        man_path = os.path.join(snaps_dir, f"{snapshot_name}.json")
        if os.path.exists(man_path) or \
                os.path.exists(os.path.join(repo_path, snapshot_name)):
            raise ResourceAlreadyExistsError(
                f"snapshot [{snapshot_name}] already exists")
        if not self.data_path:
            raise ClusterStateError("snapshots require a node data_path")
        os.makedirs(snaps_dir, exist_ok=True)
        os.makedirs(blob_dir, exist_ok=True)
        files: Dict[str, dict] = {}
        new_bytes = 0
        shared_bytes = 0
        for name in names:
            svc = self.indices[name]
            svc.flush()
            root = os.path.join(self.data_path, name)
            for dirpath, _dirs, fnames in os.walk(root):
                for fn in fnames:
                    full = os.path.join(dirpath, fn)
                    rel = os.path.join(name, os.path.relpath(full, root))
                    digest = _md5(full)
                    size = os.path.getsize(full)
                    files[rel] = {"md5": digest, "size": size}
                    blob = os.path.join(blob_dir, digest)
                    if os.path.exists(blob):
                        shared_bytes += size      # dedup hit (incremental)
                    else:
                        # atomic blob write: a crash mid-copy must never
                        # leave a truncated file at the content address —
                        # every later snapshot would dedup against it
                        shutil.copy2(full, blob + ".tmp")
                        os.replace(blob + ".tmp", blob)
                        new_bytes += size
        manifest = {"snapshot": snapshot_name, "indices": names,
                    "files": files, "ts": time.time(), "state": "SUCCESS",
                    "stats": {"new_bytes": new_bytes,
                              "shared_bytes": shared_bytes,
                              "file_count": len(files)}}
        tmp = man_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(manifest, fh)
        os.replace(tmp, man_path)
        return {"snapshot": {"snapshot": snapshot_name, "indices": names,
                             "state": "SUCCESS",
                             "stats": manifest["stats"]}}

    def _load_snapshot_manifest(self, repo_path: str, snapshot_name: str):
        import json
        man_path = os.path.join(repo_path, "snapshots",
                                f"{snapshot_name}.json")
        if os.path.exists(man_path):
            with open(man_path) as fh:
                return json.load(fh)
        raise IndexNotFoundError(f"no such snapshot [{snapshot_name}]")

    def restore(self, repo_path: str, snapshot_name: str,
                rename_pattern: Optional[str] = None,
                rename_replacement: Optional[str] = None) -> dict:
        import json
        import re as _re
        manifest = self._load_snapshot_manifest(repo_path, snapshot_name)
        blob_dir = os.path.join(repo_path, "blobs")
        restored = []
        for name in manifest["indices"]:
            target = name
            if rename_pattern:
                target = _re.sub(rename_pattern, rename_replacement or "", name)
            if target in self.indices:
                raise ResourceAlreadyExistsError(
                    f"cannot restore index [{target}]: already exists")
            prefix = name + os.sep
            for rel, meta in manifest["files"].items():
                if not rel.startswith(prefix):
                    continue
                dst = os.path.join(self.data_path, target,
                                   rel[len(prefix):])
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                shutil.copy2(os.path.join(blob_dir, meta["md5"]), dst)
            # translog/commit are part of the restored state; recover
            # normally
            meta_path = os.path.join(self.data_path, target, "index_meta.json")
            with open(meta_path) as fh:
                saved = json.load(fh)
            meta = IndexMetadata(target, settings=saved.get("settings", {}))
            self.indices[target] = IndexService(meta, saved.get("mappings"),
                                                self.data_path,
                                                thread_pools=self.thread_pools)
            self.metadata.indices[target] = meta
            self._attach_remote(target)
            restored.append(target)
        self.metadata.bump()
        return {"snapshot": {"snapshot": snapshot_name, "indices": restored,
                             "shards": {"failed": 0}}}

    # ---------------- search entry ----------------

    def _split_remote_expression(self, expression):
        """"logs,west:logs-*" -> (local names, [(alias, node, names)]).
        Reference RemoteClusterAware.groupClusterIndices."""
        local_parts: List[str] = []
        remote: List[tuple] = []
        parts = (expression if isinstance(expression, list)
                 else str(expression if expression is not None
                          else "").split(","))
        for part in parts:
            part = str(part).strip()
            alias = part.split(":", 1)[0] if ":" in part else None
            if alias is not None and alias in self.remote_clusters:
                rnode = self.remote_clusters[alias]
                sub = part.split(":", 1)[1]
                remote.append((alias, rnode, rnode.metadata.resolve(sub)))
            else:
                local_parts.append(part)
        # "" resolves to _all — only resolve locally when a local part
        # exists, else a pure-remote expression would sweep in every
        # local index
        names = (self.metadata.resolve(",".join(local_parts))
                 if local_parts and any(local_parts) else
                 (self.metadata.resolve(expression) if not remote else []))
        return names, remote

    def search(self, expression: str, body: dict, phase_hook=None,
               phase_ctx: Optional[dict] = None,
               copy_protect: bool = False,
               wlm_lane: Optional[str] = None,
               sli_lane: Optional[str] = None) -> dict:
        """`copy_protect`: caller intends to mutate the response (search
        pipeline response processors) — deep-copy it iff it aliases a
        request-cache entry, so cached entries stay pristine without taxing
        uncached paths. `wlm_lane`: serving-scheduler priority lane from
        the request's workload group (REST layer resolves it).
        `sli_lane`: the lane the per-lane SLIs and query-insights
        fingerprinting record under — defaults to `wlm_lane`, and
        differs only when the remediation actuator DEMOTED the request
        (serving/remediator.py): deprioritization changes scheduling
        priority, never accounting, or a demoted-to-batch interactive
        burn would vanish from the interactive SLO it fired.

        Flight-recorder timeline ownership: the REST facade usually
        starts the request's timeline (rest.accept); when none is
        current — direct engine callers, tests — this entry point owns
        one for the duration of the search, so every downstream event
        (scheduler, mesh, fastpath ladder) lands on a journal."""
        with self.tracer.span("indices:data/read/search",
                              index=expression) as span:
            # per-lane SLIs (docs/OBSERVABILITY.md "fleet"): every search
            # lands one requests/errors/rejected count and one latency sample
            # under its lane — the counters the time-series sampler windows
            # and the SLO burn-rate engine judges (obs/slo.py). Recorded at
            # THIS boundary so cache hits, scheduler 429s and host-loop
            # fallbacks all count exactly once.
            from ..obs import insights as _ins
            from ..utils.metrics import METRICS as _m
            from ..utils.wlm import PressureRejectedException as _rej
            lane = sli_lane or wlm_lane or "interactive"
            _t0 = time.monotonic()
            _rec = self.flight_recorder
            tl = _fr.current() if _rec.enabled else 0
            token = None
            if _rec.enabled and not tl:
                tl = _rec.start("search", index=expression,
                                node=self.node_name)
                token = _fr.set_current(tl)
            # query insights (obs/insights.py): fingerprint the body at THIS
            # boundary — the same place the per-lane SLIs land — so cache
            # hits, rejections, errors and host-ladder attribution all
            # aggregate under one bounded query shape
            obs, ins_token = _ins.begin(body if isinstance(body, dict)
                                        else {}, lane)
            try:
                resp = self._search_recorded(expression, body, phase_hook,
                                             phase_ctx, copy_protect,
                                             wlm_lane, tl, span)
            except _rej:
                _m.counter(f"search.lane.{lane}.rejected").inc()
                _ins.finish(ins_token, obs, rejected=True, timeline_id=tl)
                raise
            except BaseException as e:
                # client-side 4xx API errors (bad query, missing index) are
                # the caller's fault, not lost availability — only server
                # faults burn the error budget
                if getattr(e, "status", 500) >= 500:
                    _m.counter(f"search.lane.{lane}.errors").inc()
                    _ins.finish(ins_token, obs, error=True, timeline_id=tl)
                else:
                    _ins.finish(ins_token, obs, timeline_id=tl)
                raise
            finally:
                if token is not None:
                    _fr.reset_current(token)
            _m.counter(f"search.lane.{lane}.requests").inc()
            took_ms = (time.monotonic() - _t0) * 1000.0
            if _m.enabled:
                _m.histogram(f"search.lane.{lane}.latency_ms").record(
                    took_ms)
            _ins.finish(ins_token, obs, latency_ms=took_ms, timeline_id=tl)
            return resp

    def _search_recorded(self, expression: str, body: dict, phase_hook,
                         phase_ctx: Optional[dict], copy_protect: bool,
                         wlm_lane: Optional[str], tl: int,
                         root_span) -> dict:
        # a body the mesh already declined in this request (msearch batch
        # decline -> per-body retry) skips the mesh: one logical search
        # counts at most one mesh fallback, and the retry does no wasted
        # eligibility work. Popped BEFORE cache-key derivation so the
        # marker never perturbs request-cache identity.
        mesh_declined = bool(body.pop("_mesh_declined", False)) \
            if isinstance(body, dict) else False
        names, remote_parts = self._split_remote_expression(expression)
        from .admin import check_open
        names = check_open(self, names, expression)
        searchers = []
        gens = []
        for name in names:
            svc = self.indices[name]
            searchers.extend(svc.search_copies())
            gens.append(svc.generation)
        for alias, rnode, rnames in remote_parts:
            for rn in rnames:
                rsvc = rnode.indices[rn]
                for sid in range(rsvc.meta.num_shards):
                    searchers.append(ShardSearcher(
                        rsvc.shards[sid], shard_id=sid,
                        similarity=rsvc.default_sim,
                        index_key=f"{alias}:{rn}"))
                gens.append((alias, rn, rsvc.generation))
        _rec = self.flight_recorder
        if _rec.enabled and tl:
            _rec.record(tl, "search.start", index=expression,
                        shards=len(searchers),
                        lane=wlm_lane or "interactive")
        # request cache (deterministic bodies only; a phase hook makes the
        # response depend on pipeline state, so it bypasses the cache)
        import json as _json
        try:
            cache_key = (tuple(names), _json.dumps(body, sort_keys=True), tuple(gens))
        except TypeError:
            cache_key = None
        if phase_hook is not None:
            cache_key = None
        if cache_key is not None:
            cached = self.request_cache.get(cache_key)
            if cached is not None:
                from ..obs import insights as _ins
                _ins.note_cache_hit()
                if _rec.enabled and tl:
                    _rec.record(tl, "cache.hit", index=expression)
                if copy_protect:
                    import copy as _copy
                    return _copy.deepcopy(cached)
                return cached
        # backpressure: hard admission gate, then duress check cancels the
        # worst in-flight offender (reference SearchBackpressureService)
        self.search_backpressure.admit(self.tasks)
        self.search_backpressure.check(self.tasks)
        task = self.tasks.register("indices:data/read/search",
                                   f"indices[{expression}]")
        task.timeline_id = tl      # _tasks <-> flight-recorder linkage
        t0 = time.monotonic()
        # ladder-rung attribution for the slowlog: which fastpath rungs
        # this request exercised. A STATS delta over the request window
        # (best-effort under concurrency — concurrent searches smear into
        # each other's windows; the trace span carries the exact story)
        from ..search import fastpath as _fp
        rungs_before = dict(_fp.STATS)
        if root_span is not None:
            root_span.attributes["shards"] = len(searchers)
        try:
            if _rec.enabled and tl and root_span is not None:
                # key the timeline to the existing trace context, so
                # journals and span trees cross-reference
                _rec.annotate(tl, trace_root_id=root_span.trace_id,
                              task_id=task.id)
            resp = None
            if (len(names) == 1 and not remote_parts
                    and phase_hook is None
                    and self.indices[names[0]].mappings.star_trees):
                # star-tree composite index: eligible size=0 agg
                # requests answer from the pre-aggregated cubes
                from ..search import startree
                resp = startree.try_answer(
                    searchers, body,
                    self.indices[names[0]].mappings.star_trees)
            if (resp is None and not mesh_declined and len(names) == 1
                    and not remote_parts and phase_hook is None):
                svc0 = self.indices[names[0]]
                sched = self.serving
                if sched is not None and sched.enabled:
                    # serving scheduler: coalesce this request with
                    # concurrent eligible ones into a single batched
                    # program invocation; non-coalescable shapes
                    # bypass unchanged
                    if sched.accepts(body):
                        resp = sched.execute(names[0], svc0, body,
                                             task=task,
                                             lane=wlm_lane
                                             or "interactive")
                    else:
                        sched.note_bypass()
                        if self.mesh_service is not None:
                            resp = self.mesh_service.try_search(
                                names[0], svc0, body)
                elif self.mesh_service is not None:
                    resp = self.mesh_service.try_search(names[0], svc0,
                                                        body)
                body.pop("_mesh_declined", None)
            if resp is None:
                all_names = list(names) + [
                    f"{a}:{rn}" for a, _n, rns in remote_parts
                    for rn in rns]
                # bit-consistency gate: when an SPMD mesh owns this
                # node's hot path, OR replica read copies round-robin
                # with the primary, a host-loop execution (decline,
                # scheduler bypass, degradation, replica pick) must
                # stay byte-identical to its XLA-domain siblings —
                # the codec-v2 impact ladder serves the host-oracle
                # f32 domain instead, so it only engages when this
                # node's serving is single-domain
                # (search/impactpath.py)
                replicated = any(
                    getattr(self.indices[n], "replica_searchers",
                            None)
                    for n in names)
                tok = impactpath.mesh_attached_token(
                    self.mesh_service is not None or replicated)
                try:
                    resp = search_shards(searchers, body,
                                         index_name=",".join(all_names),
                                         task=task,
                                         phase_hook=phase_hook,
                                         phase_ctx=phase_ctx)
                finally:
                    impactpath.reset_mesh_attached(tok)
        except BaseException as e:
            if _rec.enabled and tl:
                _rec.record(tl, "search.error", error=type(e).__name__)
            raise
        finally:
            self.tasks.unregister(task)
        took = time.monotonic() - t0

        def _slow_extra(_span=root_span, _before=rungs_before):
            # built only when a slowlog threshold fires: rung deltas say
            # WHICH escalation path burned the time, the root span says
            # WHERE inside the request it went; the insights fingerprint
            # says WHAT KIND of query this was (obs/insights.py — the
            # handle into `GET /_insights/top_queries`)
            from ..obs import insights as _ins
            rungs = {k: _fp.STATS[k] - _before.get(k, 0) for k in _before
                     if _fp.STATS[k] != _before.get(k, 0)}
            _obs = _ins.current()
            return {"fastpath_rungs": rungs,
                    "rescore_path": _fp.rescore_mode(),
                    **({"fingerprint": _obs.key} if _obs is not None
                       else {}),
                    **({"trace": _span.to_dict()}
                       if _span is not None else {})}

        self.op_counters["search_total"] += 1
        self.op_counters["search_time_ms"] += took * 1000.0
        if _rec.enabled and tl:
            _rec.record(tl, "search.done",
                        took_ms=round(took * 1000.0, 3),
                        hits=resp["hits"]["total"]["value"]
                        if isinstance(resp.get("hits", {}).get("total"),
                                      dict) else None)
        for name in names:
            # slowlog entries carry the timeline id, and a threshold hit
            # triggers a flight-recorder dump (utils/slowlog.py)
            self.indices[name].search_slowlog.maybe_log(
                took, body.get("query"), extra=_slow_extra,
                timeline_id=tl)
        if len(names) == 1 and not remote_parts:
            for h in resp["hits"]["hits"]:
                h["_index"] = names[0]
        if cache_key is not None and not resp.get("timed_out"):
            # a timed-out page is whatever the budget allowed at that
            # wall-clock moment — never representative, never cached
            self.request_cache.put(cache_key, resp)
            if copy_protect:
                import copy as _copy
                resp = _copy.deepcopy(resp)
        return resp

    def msearch(self, expression: str, bodies: List[dict]) -> Optional[List[dict]]:
        """Batched msearch over one index expression. Dispatch order: the
        SPMD mesh serves eligible bodies as ONE distributed program
        invocation per group (multi-shard indices on a pod); the remainder
        fuse into grouped Pallas kernel launches (grid over queries).
        Returns None when wholly ineligible — caller falls back per-body."""
        with self.tracer.span("node.msearch", index=expression,
                              bodies=len(bodies)):
            from .admin import check_open
            names = check_open(self, self.metadata.resolve(expression),
                               expression)
            searchers = []
            for name in names:
                searchers.extend(self.indices[name].searchers)
            resps: Optional[List[Optional[dict]]] = None
            if self.mesh_service is not None and len(names) == 1:
                # ALWAYS consult the mesh — including single-shard indices it
                # will decline: try_msearch attributes the decline
                # (fallback_shapes["single_shard"]) and marks the bodies
                # `_mesh_declined`, exactly like the direct per-request path,
                # so scheduler/msearch traffic and direct traffic report
                # identical mesh attribution (and the per-body retry derives
                # identical request-cache keys — the marker is popped before
                # key derivation)
                svc = self.indices[names[0]]
                resps = self.mesh_service.try_msearch(names[0], svc, bodies)
                if all(r is None for r in resps):
                    resps = None
            if resps is None or any(r is None for r in resps):
                todo = ([i for i, r in enumerate(resps) if r is None]
                        if resps is not None else list(range(len(bodies))))
                batched = msearch_batched(searchers,
                                          [bodies[i] for i in todo],
                                          index_name=",".join(names))
                if batched is not None:
                    if resps is None:
                        resps = [None] * len(bodies)
                    for i, r in zip(todo, batched):
                        if resps[i] is None:
                            resps[i] = r
            if resps is not None and len(names) == 1:
                for resp in resps:
                    if resp is None:
                        continue       # caller runs this body per-body
                    for h in resp["hits"]["hits"]:
                        h["_index"] = names[0]
            return resps

    def stats(self) -> dict:
        out = {
            "cluster_name": self.metadata.cluster_name,
            "indices": {n: svc.stats() for n, svc in self.indices.items()},
            "breakers": self.breakers.stats(),
            "request_cache": self.request_cache.stats(),
            "tasks": self.tasks.stats(),
            "thread_pool": self.thread_pools.stats(),
            "search_pipelines": self.search_pipelines.stats(),
            "failure_detection": self.failure_detector.stats(),
            "wlm": self.wlm.stats(),
            "search_backpressure": self.search_backpressure.stats(),
            "persistent_tasks": self.persistent_tasks.stats(),
            "uptime_in_millis": int((time.monotonic() - self._start_mono)
                                    * 1000),
        }
        if self.mesh_service is not None:
            out["mesh"] = self.mesh_service.stats()
        return out
