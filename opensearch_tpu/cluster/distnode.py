"""Two-process cluster: full Nodes in separate OS processes, one index.

Each process runs a complete Node + RestClient +
HttpServer; cluster membership, state publication, and the search
scatter/gather all travel over the HTTP wire layer — the analog of the
reference's netty transport + coordinator
(`modules/transport-netty4/src/main/java/org/opensearch/transport/netty4/
Netty4Transport.java:1`, `server/src/main/java/org/opensearch/cluster/
coordination/Coordinator.java:1`, fan-out per
`action/search/TransportSearchAction.java:1`).

Design (primaries-only v1, documented):

- **Membership**: the seed node is the cluster manager. A joiner POSTs
  `/_internal/join`; the manager records it and publishes the full cluster
  state (term/version, members, per-index shard routing) to every member —
  the two-phase publish collapsed to one trusted-wire RPC.
- **Routing**: `create_index` assigns each shard an owner round-robin over
  the sorted member names. Every member creates the SAME index locally
  (same num_shards); only the owner's copy of a shard ever receives
  documents, so non-owned local shards stay empty and contribute nothing
  to that node's local scatter leg.
- **Writes**: a doc routes by `cluster.routing.shard_for(id)`; the
  coordinator forwards non-local docs to the owner's PUBLIC HTTP doc
  endpoint (the wire is the product wire, not a side channel).
- **Search = DFS_QUERY_THEN_FETCH over HTTP** (reference
  `search/dfs/DfsSearchResult.java:1` semantics):
    1. DFS: every node reports the collection statistics its own rewrite
       of the query consumes (df / collection_tf / field doc_count+sum_dl /
       maxDoc), via a recording stats context; the coordinator sums them.
    2. QUERY: every node runs its local per-shard query phase with a
       GlobalStatsContext pinned to the summed statistics — scores are
       therefore IDENTICAL to a single node holding all the data.
    3. The coordinator reduces once (`reduce_shard_results`) and
    4. FETCH: hydrates winning docs from their owning nodes.
  Internal RPC payloads are pickled (base64 in a JSON envelope) — typed
  agg partials and sort values cross the wire losslessly; the reference's
  transport is binary object serialization for the same reason. The
  `/_internal/*` surface is a trusted node-to-node wire (security is a
  declared exclusion, SURVEY §2.9).
- **Failure domain** (docs/RESILIENCE.md): every `/_internal` RPC
  carries the request's remaining deadline budget (`deadline_ctx`,
  stamped exactly like the `trace_ctx`/`obs_ctx` pair) and derives its
  socket timeout from it — `min(remaining, cap)` instead of a fixed
  per-hop 30 s; a hop arriving with an exhausted budget answers an
  immediate 408 shard failure. A failed shard RPC retries in place with
  jittered exponential backoff under a per-request retry budget, then
  FAILS OVER to the shard's next copy (`number_of_node_replicas` copies
  assigned at create_index; `MemberFailureDetector` findings demote
  suspect members in the preference order). A shard with no live copy
  left fails honestly: `_shards.failed` with per-shard reasons,
  `timed_out`/`terminated_early` response flags, and
  `allow_partial_search_results=false` converting any partiality into a
  whole-request error (reference parity). Fetch never fails over — doc
  coordinates are copy-local, so fetch sticks to the copy that ran the
  query phase (reference query-and-fetch affinity) and a copy lost
  between phases fails its shard. The seeded chaos harness
  (`cluster/faults.py`) injects drop/delay/error/blackhole at the RPC
  send/receive sites so the kill-one-node and deadline tests replay
  exact interleavings.

Unsupported on a distributed index (explicit 400, never silently wrong):
non-`_score` sorts, collapse, rescore, search_after/scroll/PIT, suggest,
profile, knn, and aggregations with sub-aggregations (their coordinator
refinement needs cross-node sub-searches; reference parity for those is
future work).
"""

from __future__ import annotations

import base64
import contextlib
import contextvars
import json
import os
import pickle
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

from ..rest.client import ApiError, RestClient
from ..rest.http_server import HttpServer
from ..search import plan as PL
from ..search import query_dsl as dsl
from ..search.aggregations import parse_aggs
from ..search.executor import (Candidate, ShardQueryResult,
                               _global_stats_contexts, reduce_shard_results)
from ..utils import deadline as _dl
from ..utils import legs as _legs
from . import faults as _faults
from .failure import MemberFailureDetector
from .node import Node
from .routing import assign_copies, order_copies, shard_for

# transport cap, NOT the per-hop timeout: every RPC derives its actual
# socket timeout from the request's remaining deadline budget
# (min(remaining, cap)); only deadline-less requests see the full cap
_RPC_TIMEOUT_CAP_S = 30.0

# observability scrapes (cluster stats / hot_threads / history fan-out)
# get a TIGHTER default cap: a monitoring poll against a wedged member
# must degrade to a per-node `failed` entry in seconds, never hold the
# coordinator for the full transport cap. A live request deadline still
# tightens it further (deadline-ctx rides the scrape like any RPC).
_SCRAPE_CAP_S = 5.0

# Failure-detector snapshot for one top-level request.  A hybrid body
# fans its sub-retrievals out as parallel legs; each sub-search plans
# its scatter from the detector-deprioritized member set, and a plan
# taken mid-request would otherwise depend on WHEN a sibling leg's
# failure landed in the detector — a thread race.  The hybrid entry
# point snapshots the set once, and every leg (the contextvar rides
# the leg's captured context) plans against that same view, so the
# serial and parallel arms issue the same RPCs and seeded chaos
# journals stay byte-identical across arms.  Mid-request failures
# still drive retries/failover through the per-request plan state.
_fd_snap: contextvars.ContextVar[Optional[frozenset]] = \
    contextvars.ContextVar("ostpu_fd_snapshot", default=None)


class RetryPolicy:
    """Per-shard retry + failover knobs (docs/RESILIENCE.md). In-place
    retries are jittered-exponential-backoff re-sends to the SAME member
    (transient blips); the per-request `budget` bounds total retries
    across all shards so a sick cluster degrades to honest shard
    failures instead of a retry storm; `storm_n` is the request-level
    retry count that freezes a flight-recorder dump."""

    def __init__(self, same_member_retries: int = 1,
                 budget: int = 4,
                 base_backoff_s: float = 0.025,
                 backoff_mult: float = 2.0,
                 max_backoff_s: float = 0.5,
                 storm_n: Optional[int] = None):
        self.same_member_retries = int(same_member_retries)
        self.budget = int(budget)
        self.base_backoff_s = float(base_backoff_s)
        self.backoff_mult = float(backoff_mult)
        self.max_backoff_s = float(max_backoff_s)
        # storm threshold defaults to the retry budget: a request that
        # burns its WHOLE budget is the forensic moment (a default
        # above the budget would make the dump unreachable — retries
        # are capped at the budget)
        self.storm_n = int(storm_n if storm_n is not None
                           else self.budget)


class _ShardCallFailed(Exception):
    """One member terminally failed a shard-group call (retries spent).
    `reason` is the per-shard failure record the response surfaces."""

    def __init__(self, member: str, kind: str, attempts: int):
        super().__init__(f"[{member}] {kind} after {attempts} attempt(s)")
        self.member = member
        self.kind = kind
        self.attempts = attempts


class _RequestState:
    """Per-request resilience accounting: the deadline, the shared retry
    budget, the deterministic backoff RNGs, and the flags/failure
    reasons the response assembly reads. Member legs of one request run
    CONCURRENTLY (utils/legs.py), so the retry budget is taken under a
    lock and the backoff jitter is drawn from a per-(member, leg) RNG
    seeded from the installed chaos schedule via a stable hash — thread
    interleaving can change neither a leg's jitter sequence nor a
    replay's."""

    def __init__(self, policy: RetryPolicy, dl, tl: int):
        self.policy = policy
        self.dl = dl
        self.tl = tl
        self.retries = 0
        self.failovers = 0
        self.timed_out = False
        self.storm_fired = False
        sched = _faults.installed()
        self._chaos_seed = sched.seed if sched is not None else None
        self._lock = threading.Lock()
        self._rngs: Dict[tuple, random.Random] = {}

    def rpc_timeout_s(self) -> float:
        if self.dl is None:
            return _RPC_TIMEOUT_CAP_S
        return self.dl.rpc_timeout_s(_RPC_TIMEOUT_CAP_S)

    def take_retry(self) -> bool:
        with self._lock:
            if self.retries >= self.policy.budget:
                return False
            self.retries += 1
            return True

    def _rng_for(self, member: Optional[str]) -> random.Random:
        key = (member, _legs.current_path())
        with self._lock:
            rng = self._rngs.get(key)
            if rng is None:
                if self._chaos_seed is None:
                    rng = random.Random()
                else:
                    import hashlib
                    h = hashlib.sha256(
                        f"{self._chaos_seed}|{key[0]}|{key[1]}"
                        .encode()).digest()
                    rng = random.Random(int.from_bytes(h[:8], "big"))
                self._rngs[key] = rng
            return rng

    def backoff_s(self, attempt: int,
                  member: Optional[str] = None) -> float:
        """Full-jitter exponential backoff, bounded by the cap and by
        the remaining deadline (never sleep past the budget)."""
        p = self.policy
        ceil = min(p.base_backoff_s * (p.backoff_mult ** max(attempt - 1,
                                                             0)),
                   p.max_backoff_s)
        b = self._rng_for(member).uniform(0.0, ceil)
        if self.dl is not None:
            b = min(b, max(self.dl.remaining_s(), 0.0))
        return b


# ---------------------------------------------------------------------
# statistics contexts for the cross-node DFS phase
# ---------------------------------------------------------------------

class RecordingStatsContext(PL.ShardContext):
    """Wraps the local collection-stats view and records every statistic
    the query rewrite consumes — the node-local half of the DFS phase."""

    def __init__(self, mappings, segments, similarity=None,
                 field_similarities=None):
        super().__init__(mappings, segments, similarity, field_similarities)
        self.rec = {"num_docs": 0, "df": {}, "ctf": {}, "fs": {}}

    @property
    def num_docs(self) -> int:
        n = PL.ShardContext.num_docs.fget(self)
        self.rec["num_docs"] = n
        return n

    def doc_freq(self, field: str, term: str) -> int:
        v = super().doc_freq(field, term)
        self.rec["df"][(field, term)] = v
        return v

    def collection_tf(self, field: str, term: str) -> float:
        v = super().collection_tf(field, term)
        self.rec["ctf"][(field, term)] = v
        return v

    def field_stats(self, field: str) -> Tuple[int, int]:
        v = super().field_stats(field)
        self.rec["fs"][field] = v
        return v


class GlobalStatsContext(PL.ShardContext):
    """A stats context pinned to coordinator-summed global statistics: every
    node scores with the same idf/avgdl no matter where documents live.
    Statistics the DFS recording did not capture (rare: a fetch-side
    feature asking about a term the query rewrite never touched) fall back
    to local values — degraded, never crashing."""

    def __init__(self, mappings, segments, similarity, field_similarities,
                 g: dict):
        super().__init__(mappings, segments, similarity, field_similarities)
        self._g = g

    @property
    def num_docs(self) -> int:
        return self._g["num_docs"]

    def doc_freq(self, field: str, term: str) -> int:
        v = self._g["df"].get((field, term))
        return v if v is not None else super().doc_freq(field, term)

    def collection_tf(self, field: str, term: str) -> float:
        v = self._g["ctf"].get((field, term))
        return v if v is not None else super().collection_tf(field, term)

    def field_stats(self, field: str) -> Tuple[int, int]:
        v = self._g["fs"].get(field)
        return tuple(v) if v is not None else super().field_stats(field)


def _merge_dfs(parts: List[dict]) -> dict:
    g = {"num_docs": 0, "df": {}, "ctf": {}, "fs": {}}
    for p in parts:
        g["num_docs"] += p["num_docs"]
        for k, v in p["df"].items():
            g["df"][k] = g["df"].get(k, 0) + v
        for k, v in p["ctf"].items():
            g["ctf"][k] = g["ctf"].get(k, 0.0) + v
        for k, (dc, sdl) in p["fs"].items():
            odc, osdl = g["fs"].get(k, (0, 0))
            g["fs"][k] = (odc + dc, osdl + sdl)
    return g


# ---------------------------------------------------------------------
# wire helpers
# ---------------------------------------------------------------------

def _b64(obj) -> str:
    return base64.b64encode(pickle.dumps(obj)).decode("ascii")


def _unb64(s: str):
    return pickle.loads(base64.b64decode(s.encode("ascii")))


def _http(addr: str, method: str, path: str, payload=None,
          timeout: float = _RPC_TIMEOUT_CAP_S) -> dict:
    data = json.dumps(payload).encode() if payload is not None else None
    headers = {"Content-Type": "application/json"}
    # shared-secret node-to-node trust: when the cluster runs with REST
    # security enabled, every /_internal call must carry this token (the
    # compact analog of the reference's transport-layer TLS mutual auth)
    tok = os.environ.get("OPENSEARCH_TPU_CLUSTER_TOKEN")
    if tok:
        headers["X-Cluster-Token"] = tok
    req = urllib.request.Request(
        f"http://{addr}{path}", data=data, method=method,
        headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read().decode()
    return json.loads(raw) if raw else {}


class NodeUnreachable(Exception):
    pass


# ---------------------------------------------------------------------
# the distributed node
# ---------------------------------------------------------------------

class DistClusterNode:
    """A full Node + HTTP server participating in a multi-process cluster.

    Public surface: `create_index`, `index_doc`, `refresh`, `search`,
    `get`, `cluster_state`, `stop`. Everything travels over HTTP — this
    object is also the handler for `/_internal/*` RPCs on its server.
    """

    def __init__(self, name: str, seed: Optional[str] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 retry_policy: Optional[RetryPolicy] = None):
        self.name = name
        self.node = Node()
        self.client = RestClient(node=self.node)
        self.server = HttpServer(self.client, host=host, port=port)
        self.server.dist = self
        self.port = self.server.start()
        self.addr = f"{host}:{self.port}"
        self._lock = threading.RLock()
        # cluster state (reference ClusterState: term/version + routing)
        self.term = 1
        self.version = 0
        self.leader = name if seed is None else None
        self.members: Dict[str, str] = {name: self.addr}
        # primary owner per shard (back-compat view of copies[...][0])
        self.routing: Dict[str, Dict[int, str]] = {}   # index -> shard -> node
        # full copy lists, primary first (index -> shard -> [members])
        self.copies: Dict[str, Dict[int, List[str]]] = {}
        self.index_bodies: Dict[str, dict] = {}
        self.retry_policy = retry_policy or RetryPolicy()
        # member-level failure detection feeding copy selection: suspect
        # members are demoted in every shard's preference order until a
        # successful probe/RPC (cluster/failure.py)
        self.member_fd = MemberFailureDetector()
        # wire the detector into an already-armed remediation actuator
        # (one armed on the Node BEFORE this cluster wrapper exists):
        # without this, the deprioritize_member action would be silently
        # inert
        rem = self.node.remediation
        if rem is not None and rem.member_fd is None:
            rem.member_fd = self.member_fd
        # registry this node answers fleet scrapes from. None -> the
        # process-default METRICS (the one-node-per-process deployment);
        # in-process multi-node tests inject distinct registries so the
        # merge math federates genuinely disjoint streams
        self.obs_registry = None
        # insights engine this node answers `/_internal/insights` from.
        # None -> the process-default INSIGHTS; in-process multi-node
        # tests inject distinct engines so the heavy-hitter merge
        # federates genuinely disjoint workloads (the obs_registry
        # pattern above)
        self.insights_engine = None
        # remediation actuator this node's admission path consults and
        # `/_internal/remediation` answers from. None -> the
        # process-default REMEDIATOR; the traffic harness injects
        # per-node instances (same pattern as insights_engine)
        self.remediation_engine = None
        if seed is not None:
            st = _http(seed, "POST", "/_internal/join",
                       {"name": name, "addr": self.addr})
            self._apply_state(st["state"])

    # ---------------- state machine ----------------

    def _state(self) -> dict:
        # snapshot under the (reentrant) state lock, copying the member
        # and body maps: publishes json.dumps this dict OUTSIDE the lock
        # (OSL702 fan-out), so handing out live references let a
        # concurrent join blow up the serializer ("dict changed size
        # during iteration") or ship different member sets per target
        with self._lock:
            return {"term": self.term, "version": self.version,
                    "leader": self.leader, "members": dict(self.members),
                    "routing": {i: {str(s): n for s, n in r.items()}
                                for i, r in self.routing.items()},
                    "copies": {i: {str(s): list(c) for s, c in r.items()}
                               for i, r in self.copies.items()},
                    "index_bodies": dict(self.index_bodies)}

    def _apply_state(self, st: dict) -> None:
        with self._lock:
            # Publish fan-outs run unserialized (outside the state
            # lock), so a slow send can deliver version N after a fast
            # one delivered N+1; applying it would regress to stale
            # state and silently drop the newer member/index. Ignore
            # anything not strictly newer (a higher term always wins).
            if (st["term"], st["version"]) <= (self.term, self.version):
                return
            self.term = st["term"]
            self.version = st["version"]
            self.leader = st["leader"]
            self.members = dict(st["members"])
            self.routing = {i: {int(s): n for s, n in r.items()}
                            for i, r in st["routing"].items()}
            # pre-copies states (rolling upgrade shape): primaries only
            self.copies = {i: {int(s): list(c) for s, c in r.items()}
                           for i, r in st.get("copies", {}).items()}
            for i, r in self.routing.items():
                self.copies.setdefault(i, {s: [n] for s, n in r.items()})
            self.index_bodies = dict(st["index_bodies"])
            # idempotently materialize any index this node doesn't have yet
            for iname, body in self.index_bodies.items():
                if iname not in self.node.indices:
                    self.client.indices.create(iname, body)

    def _publish(self) -> None:
        """Leader: bump version, push full state to every member (self
        applies synchronously). Unreachable members keep their shards in
        the routing table; searches report them failed until they rejoin."""
        # bump + snapshot under the (reentrant) state lock: the unlocked
        # bump raced `_apply_state`'s locked `self.version = st["version"]`
        with self._lock:
            self.version += 1
            st = self._state()
        from ..utils.metrics import METRICS
        for name, addr in list(self.members.items()):
            if name == self.name:
                continue
            try:
                _http(addr, "POST", "/_internal/publish", {"state": st})
            except (urllib.error.URLError, OSError):
                # best-effort publish by design — but never silently:
                # the member keeps its shards in routing and searches
                # report them failed until it rejoins (OSL508)
                METRICS.counter("dist.publish.failed").inc()

    # ---------------- internal RPC handler (called by HttpServer) --------

    def handle_internal(self, method: str, parts: List[str], body: dict
                        ) -> Tuple[int, dict]:
        op = parts[1] if len(parts) > 1 else ""
        if _faults.enabled():
            # serving-side chaos site: a rule here makes THIS node the
            # slow/flaky one (cluster/faults.py)
            _faults.on_rpc_recv(self.name, op)
        if op == "ping" and method == "GET":
            # failure-detector probe target (cluster/failure.py)
            return 200, {"ok": True, "node": self.name}
        if op == "join" and method == "POST":
            # record the member under the lock, but fan the publish out
            # AFTER releasing it: _publish RPCs every member, and holding
            # the state lock across those sends serialized every other
            # join/search-route against the slowest member (OSL702)
            with self._lock:
                self.members[body["name"]] = body["addr"]
            self._publish()
            with self._lock:
                return 200, {"state": self._state()}
        if op == "publish" and method == "POST":
            self._apply_state(body["state"])
            return 200, {"acknowledged": True}
        if op in ("dfs", "query_phase", "fetch_phase",
                  "stats", "node_stats", "hot_threads", "history",
                  "insights", "remediation", "indexing"):
            # deadline propagation: re-anchor the remaining budget the
            # coordinator stamped; an already-exhausted budget answers an
            # immediate 408 shard failure instead of a full local phase
            # (observability scrapes ride the same contract — a fleet
            # poll under a request deadline degrades honestly)
            dl = _dl.Deadline.from_wire(body.get("deadline_ctx"))
            if dl is not None and dl.exhausted():
                from ..utils.metrics import METRICS
                METRICS.counter("dist.deadline.expired_on_arrival").inc()
                return 408, {"error": {
                    "type": "request_timeout_exception",
                    "reason": f"[{op}] arrived with an exhausted "
                              f"deadline budget"}}
            with _dl.scope(dl):
                if op in ("stats", "node_stats", "hot_threads",
                          "history", "insights", "remediation",
                          "indexing"):
                    return 200, self._handle_obs(op, body)
                return self._handle_phase(op, body)
        if op == "state" and method == "GET":
            return 200, {"state": self._state()}
        if op == "create_index" and method == "POST":
            return 200, self.create_index(parts[2], body)
        if op == "search" and method == "POST":
            # run a DISTRIBUTED search coordinated by THIS node (any member
            # can coordinate, like any reference node with the coordinator
            # role); the origin lane rides the payload so remediation
            # admission and per-lane SLIs hold on this path too
            return 200, self.search(body["index"], body["body"],
                                    lane=body.get("lane", "interactive"))
        return 404, {"error": {"type": "resource_not_found_exception",
                               "reason": f"unknown internal op [{op}]"}}

    def _handle_phase(self, op: str, body: dict) -> Tuple[int, dict]:
        shards = ([int(s) for s in body["shards"]]
                  if body.get("shards") is not None else None)
        if op == "dfs":
            with self._rpc_span("dist.dfs", body) as s, \
                    self._rpc_timeline("dfs", body) as rtl:
                recs = self._local_dfs(body["index"], body["body"],
                                       shards)
            return 200, {"recs": _b64(recs), "span": self._span_out(s),
                         "obs": self._obs_out(rtl)}
        if op == "query_phase":
            with self._rpc_span("dist.query_phase", body) as s, \
                    self._rpc_timeline("query_phase", body) as rtl:
                results = self._local_query(body["index"], body["body"],
                                            _unb64(body["g"]), shards)
            return 200, {"results": _b64(results),
                         "span": self._span_out(s),
                         "obs": self._obs_out(rtl)}
        with self._rpc_span("dist.fetch_phase", body) as s, \
                self._rpc_timeline("fetch_phase", body) as rtl:
            hits = self._local_fetch(body["index"], body["body"],
                                     int(body["shard"]),
                                     _unb64(body["cands"]),
                                     _unb64(body["g"]))
        return 200, {"hits": _b64(hits), "span": self._span_out(s),
                     "obs": self._obs_out(rtl)}

    # ---------------- trace propagation over the wire ----------------
    #
    # The coordinator stamps every /_internal RPC payload with its trace
    # context (`trace_ctx`); the serving node runs the local phase under a
    # span carrying that context and RETURNS the finished span tree in
    # the response, which the coordinator grafts under its own phase span
    # (`TRACER.attach_remote`) — so one distributed search reads as ONE
    # coherent parent-child trace on the coordinating node, while each
    # member's ring still holds its local half, attributable via the
    # stamped parent ids.

    def _rpc_span(self, name: str, body: dict):
        from ..utils.trace import TRACER
        tctx = body.get("trace_ctx") or {}
        return TRACER.span(name, node=self.name,
                           **{k: tctx[k] for k in
                              ("trace_root_id", "parent_span_id",
                               "coordinator") if k in tctx})

    @staticmethod
    def _span_out(s) -> Optional[dict]:
        return s.to_dict() if s is not None else None

    # ---------------- flight-recorder stitching over the wire ---------
    #
    # Mirrors the trace propagation above: the coordinator stamps its
    # (node, timeline) onto every RPC; the serving node runs the local
    # phase under its OWN timeline carrying the origin linkage, and the
    # response returns that timeline's events, which the coordinator
    # grafts into the request's journal (`RECORDER.graft`) — so one
    # distributed search reads as ONE stitched cross-node timeline.

    @contextlib.contextmanager
    def _rpc_timeline(self, op: str, body: dict):
        from ..obs import flight_recorder as _fr
        ctx = body.get("obs_ctx")
        if not _fr.RECORDER.enabled or not isinstance(ctx, dict):
            yield 0
            return
        tl = _fr.RECORDER.start(f"rpc.{op}", node=self.name,
                                origin_node=ctx.get("node"),
                                origin_timeline=ctx.get("timeline"))
        token = _fr.set_current(tl)
        try:
            if tl:
                _fr.RECORDER.record(tl, "rpc.accept", op=op,
                                    node=self.name)
            yield tl
        finally:
            _fr.reset_current(token)

    @staticmethod
    def _obs_out(tl: int) -> Optional[list]:
        if not tl:
            return None
        from ..obs import flight_recorder as _fr
        return _fr.RECORDER.timeline_events(tl)

    def _rpc(self, member: str, op: str, payload: dict,
             timeout_s: Optional[float] = None,
             dl: Optional[_dl.Deadline] = None) -> dict:
        """Coordinator-side RPC with trace stamping + span grafting +
        flight-recorder timeline stitching + deadline propagation +
        latency accounting. The socket timeout is deadline-derived
        (min(remaining, cap)); the remaining budget rides the payload as
        `deadline_ctx` exactly like `trace_ctx`/`obs_ctx` do."""
        from ..obs import flight_recorder as _fr
        from ..utils.metrics import METRICS
        from ..utils.trace import TRACER
        if dl is None:
            dl = _dl.current()
        if timeout_s is None:
            timeout_s = (dl.rpc_timeout_s(_RPC_TIMEOUT_CAP_S)
                         if dl is not None else _RPC_TIMEOUT_CAP_S)
        wctx = TRACER.wire_context()
        if wctx is not None:
            payload = dict(payload,
                           trace_ctx=dict(wctx, coordinator=self.name))
        tl = _fr.current() if _fr.RECORDER.enabled else 0
        if tl:
            payload = dict(payload,
                           obs_ctx={"node": self.name, "timeline": tl})
        if dl is not None:
            # stamped at send time: the receiving hop re-anchors what is
            # left, so queue/transit time is charged to the budget
            payload = dict(payload, deadline_ctx=dl.to_wire())
        t0 = time.monotonic()
        try:
            if _faults.enabled():
                # inside the try: injected faults go through the SAME
                # failure accounting (metrics, detector, events) as real
                # ones — the harness must not produce divergent journals
                _faults.on_rpc_send(member, op, timeout_s)
            r = _http(self.members[member], "POST", f"/_internal/{op}",
                      payload, timeout=timeout_s)
        except urllib.error.HTTPError as e:
            if e.code < 500:
                # the member ANSWERED (408 deadline refusal, 4xx API
                # error): that is member health, not member death — no
                # detector demotion, no transport-failure count
                raise
            METRICS.counter("dist.rpc.failed").inc()
            self.member_fd.note_failure(member)
            if tl:
                _fr.RECORDER.record(tl, "rpc.failed", op=op, node=member)
            raise
        except Exception:
            METRICS.counter("dist.rpc.failed").inc()
            self.member_fd.note_failure(member)
            if tl:
                _fr.RECORDER.record(tl, "rpc.failed", op=op, node=member)
            raise
        self.member_fd.note_success(member)
        METRICS.histogram(f"dist.rpc.{op}").record(
            (time.monotonic() - t0) * 1000.0)
        TRACER.attach_remote(r.get("span"))
        _fr.RECORDER.graft(tl, r.get("obs"), node=member)
        return r

    def _rpc_failsafe(self, member: str, op: str, payload: dict,
                      rs: _RequestState) -> dict:
        """`_rpc` under the retry policy: in-place re-sends with jittered
        exponential backoff for transient failures, bounded by the
        per-request retry budget and the deadline. Terminal outcomes:

        - `DeadlineExhausted` — the budget ran out (locally, or the
          remote answered 408); never retried, the shard fails with a
          timeout reason and the response gets `timed_out: true`.
        - `_ShardCallFailed` — retries spent; the caller fails the
          shard over to its next copy (`rpc.failover`) or surfaces it.
        - Any non-5xx HTTPError — a genuine API error (e.g. 400),
          re-raised untouched.
        """
        from ..obs import flight_recorder as _fr
        from ..utils.metrics import METRICS
        attempts = 0
        while True:
            if rs.dl is not None and rs.dl.exhausted():
                rs.timed_out = True
                METRICS.counter("dist.deadline.exhausted").inc()
                if rs.tl:
                    _fr.RECORDER.record(rs.tl, "deadline.exhausted",
                                        op=op, node=member)
                raise _dl.DeadlineExhausted(
                    f"[{op}] to [{member}]: request budget exhausted")
            try:
                return self._rpc(member, op, payload,
                                 timeout_s=rs.rpc_timeout_s(), dl=rs.dl)
            except urllib.error.HTTPError as e:
                if e.code == 408:
                    # the hop measured the budget exhausted — retrying
                    # cannot help inside the same budget
                    rs.timed_out = True
                    METRICS.counter("dist.deadline.exhausted").inc()
                    if rs.tl:
                        _fr.RECORDER.record(rs.tl, "deadline.exhausted",
                                            op=op, node=member)
                    raise _dl.DeadlineExhausted(
                        f"[{member}] rejected [{op}]: budget exhausted")
                if e.code < 500:
                    raise
                kind = "internal_error"
            except (urllib.error.URLError, TimeoutError, OSError):
                kind = "node_unreachable"
            attempts += 1
            if attempts > rs.policy.same_member_retries \
                    or not rs.take_retry():
                raise _ShardCallFailed(member, kind, attempts)
            backoff = rs.backoff_s(attempts, member=member)
            METRICS.counter("dist.rpc.retry").inc()
            METRICS.histogram("dist.rpc.backoff_ms").record(
                backoff * 1000.0)
            if rs.tl:
                _fr.RECORDER.record(rs.tl, "rpc.retry", op=op,
                                    node=member, attempt=attempts,
                                    backoff_ms=round(backoff * 1000.0, 3))
            if not rs.storm_fired and rs.retries >= rs.policy.storm_n:
                # retry storm: the forensic moment — freeze the journal
                # before the request degrades further
                rs.storm_fired = True
                if _fr.RECORDER.enabled and rs.tl:
                    _fr.RECORDER.trigger(
                        "retry_storm", [rs.tl],
                        note=f"{rs.retries} retries in one request "
                             f"(storm_n={rs.policy.storm_n})")
            if backoff > 0:
                time.sleep(backoff)

    # ---------------- cluster API ----------------

    def cluster_state(self) -> dict:
        return self._state()

    @staticmethod
    def _node_replicas(body: dict) -> int:
        """`index.number_of_node_replicas` — CROSS-NODE shard copies
        (distinct from `number_of_replicas`, which allocates intra-node
        device copies). Default 0: primaries-only, the pre-resilience
        layout."""
        settings = (body or {}).get("settings", {}) or {}
        v = settings.get("index", {}).get(
            "number_of_node_replicas",
            settings.get("number_of_node_replicas", 0))
        return max(int(v), 0)

    def create_index(self, name: str, body: dict) -> dict:
        """Leader-only (forwarded if called on a follower): create on
        every member, assign each shard an ordered COPY list (primary
        first, `number_of_node_replicas` extra members) round-robin over
        sorted member names."""
        if self.leader != self.name:
            return _http(self.members[self.leader], "POST",
                         f"/_internal/create_index/{name}", body)
        # mutate routing state under the lock, then fan the member PUTs
        # and the publish out AFTER releasing it: a slow/dead member
        # otherwise blocks every search-route and join for the full HTTP
        # timeout while we hold the state lock (OSL702). The snapshots
        # taken under the lock keep the returned routing/copies coherent
        # even if a concurrent create lands between release and return.
        with self._lock:
            self.client.indices.create(name, body)
            n_shards = self.node.indices[name].meta.num_shards
            copies = assign_copies(
                n_shards, self.members, 1 + self._node_replicas(body))
            routing = {s: c[0] for s, c in copies.items()}
            self.copies[name] = copies
            self.routing[name] = routing
            self.index_bodies[name] = body
            targets = [(m, a) for m, a in self.members.items()
                       if m != self.name]
        for _mname, addr in targets:
            _http(addr, "PUT", f"/{name}", body)
        self._publish()
        return {"acknowledged": True, "index": name,
                "routing": routing, "copies": copies}

    def index_doc(self, index: str, doc: dict, id: str,
                  refresh: bool = False) -> dict:
        """Route by doc id; write through EVERY copy holder of the doc's
        shard (primary first) over the public doc endpoint — copies stay
        byte-identical when writers are externally ordered (one
        coordinator per doc id, the bulk-load shape): every holder then
        applies the same doc stream in the same order. CONCURRENT
        same-id writes through different coordinators can interleave
        differently per holder (no primary sequencing yet — reference
        primary-term ordering is future work). A primary failure fails
        the write with
        nothing applied; a REPLICA failure after the primary applied is
        surfaced as a 500 naming the diverged copy (counted in
        `dist.replica_write_failed`) — the caller must retry or drop the
        copy; silent divergence would poison failover byte-identity
        (stale-copy repair is future work)."""
        import time as _t

        from ..obs import ingest_obs as _iobs
        from ..utils.metrics import METRICS
        r = self.routing.get(index)
        if r is None:
            raise ApiError(404, "index_not_found_exception",
                           f"no such index [{index}]")
        n = self.node.indices[index].meta.num_shards
        shard = shard_for(id, n)
        holders = self.copies.get(index, {}).get(shard, [r[shard]])
        refresh_q = "?refresh=true" if refresh else ""
        t0 = _t.perf_counter()
        out = None
        for ord_, holder in enumerate(holders):
            try:
                if holder == self.name:
                    res = self.client.index(index, doc, id=id,
                                            refresh=refresh)
                else:
                    res = _http(self.members[holder], "PUT",
                                f"/{index}/_doc/{id}{refresh_q}", doc)
            except (urllib.error.URLError, OSError) as e:
                if ord_ == 0:
                    raise   # primary never applied: clean failure
                METRICS.counter("dist.replica_write_failed").inc()
                _iobs.count("indexing.replica.failed")
                raise ApiError(
                    500, "replica_write_exception",
                    f"doc [{id}] applied on {holders[:ord_]} but copy "
                    f"[{holder}] failed ({type(e).__name__}): copies "
                    f"have diverged — retry the write or remove the "
                    f"copy")
            if out is None:
                out = res
        if len(holders) > 1 and _iobs.enabled():
            # whole-fanout wall time (primary + every copy), the
            # write-through analog of the replica sync span
            METRICS.counter("indexing.replica.write_through").inc(
                len(holders) - 1)
            METRICS.histogram("indexing.replica.fanout_ms").record(
                (_t.perf_counter() - t0) * 1000.0)
        return out

    def get(self, index: str, id: str) -> dict:
        owner = self._owner(index, id)
        if owner == self.name:
            return self.client.get(index, id)
        try:
            return _http(self.members[owner], "GET", f"/{index}/_doc/{id}")
        except urllib.error.HTTPError as e:
            raise ApiError(e.code, "resource_not_found_exception",
                           f"[{id}] not found")

    def refresh(self, index: str) -> None:
        from ..utils.metrics import METRICS
        self.client.indices.refresh(index)
        for mname, addr in self.members.items():
            if mname == self.name:
                continue
            try:
                _http(addr, "POST", f"/{index}/_refresh")
            except (urllib.error.URLError, OSError):
                # an unreachable member misses the refresh; its copies
                # serve stale until it rejoins — counted, never silent
                # (OSL508). Mirrored into the write-path failure family
                # so the ingest observatory sees it too.
                METRICS.counter("dist.refresh.failed").inc()
                from ..obs import ingest_obs as _iobs
                _iobs.count("indexing.refresh.fanout_failed")

    def _owner(self, index: str, id: str) -> str:
        r = self.routing.get(index)
        if r is None:
            raise ApiError(404, "index_not_found_exception",
                           f"no such index [{index}]")
        n = self.node.indices[index].meta.num_shards
        return r[shard_for(id, n)]

    # ---------------- distributed search ----------------

    # knn left this list with the hybrid-retrieval subsystem (PR 15):
    # the per-shard knn program needs no cross-shard state beyond the
    # DFS stats that already ride every scatter, so both the ES-style
    # top-level `knn` section and `query.knn` serve distributed now
    _UNSUPPORTED = ("collapse", "rescore", "search_after", "suggest",
                    "profile", "scroll", "pit")

    def _check_supported(self, body: dict) -> List:
        for k in self._UNSUPPORTED:
            if body.get(k):
                raise ApiError(400, "illegal_argument_exception",
                               f"[{k}] is not supported on a distributed "
                               f"index")
        for s in body.get("sort", []):
            f = s if isinstance(s, str) else next(iter(s))
            if f != "_score":
                raise ApiError(400, "illegal_argument_exception",
                               "only _score sort is supported on a "
                               "distributed index")
        agg_nodes = parse_aggs(body.get("aggs", body.get("aggregations")))
        for an in (agg_nodes or []):
            if an.subs:
                raise ApiError(400, "illegal_argument_exception",
                               "sub-aggregations are not supported on a "
                               "distributed index")
        return agg_nodes or []

    def _local_dfs(self, index: str, body: dict,
                   shards: Optional[List[int]] = None) -> Dict[int, dict]:
        """Per-SHARD collection statistics (the coordinator sums exactly
        one copy of every shard, so replicated copies never double-count
        df/avgdl). `shards=None` covers every local shard — a
        convenience for direct callers/tests; the search path always
        sends an explicit plan."""
        svc = self.node.indices[index]
        searchers = svc.searchers
        if shards is None:
            shards = list(range(len(searchers)))
        out: Dict[int, dict] = {}
        for sid in shards:
            segs = list(searchers[sid].engine.segments)
            ctx = RecordingStatsContext(
                svc.mappings, segs, svc.default_sim,
                getattr(svc, "field_similarities", None))
            try:
                from ..search.executor import _collect_named
                lroot = PL.rewrite(dsl.parse_query(body.get("query")), ctx,
                                   scoring=True)
                # named queries are fetch-side state that does not cross
                # the wire yet; piggyback the check on the rewrite DFS
                # already does
                ctx.rec["named"] = bool(_collect_named(lroot))
            except dsl.QueryParseError:
                pass
            _ = ctx.num_docs      # maxDoc is always part of the DFS result
            # avgdl (per-field doc_count + sum_dl) is consumed at the
            # prepare stage, not rewrite — record it for every text field
            # this shard holds so the merged fs covers whatever the query
            # touches
            for s in segs:
                for f in s.text_stats:
                    ctx.field_stats(f)
            out[sid] = ctx.rec
        return out

    def _global_ctx(self, index: str, g: dict) -> GlobalStatsContext:
        svc = self.node.indices[index]
        segs = [s for sr in svc.searchers for s in sr.engine.segments]
        return GlobalStatsContext(svc.mappings, segs, svc.default_sim,
                                  getattr(svc, "field_similarities", None),
                                  g)

    def _local_query(self, index: str, body: dict, g: dict,
                     shards: Optional[List[int]] = None
                     ) -> List[ShardQueryResult]:
        """Query phase for the REQUESTED shards (the coordinator's plan
        assigns each shard to exactly one live copy holder) with global
        stats; results stripped of segment references (they do not cross
        the wire). `shards=None` runs every local shard — direct
        callers/tests only; the search path always sends a plan."""
        svc = self.node.indices[index]
        ctx = self._global_ctx(index, g)
        if shards is None:
            shards = list(range(len(svc.searchers)))
        out = []
        for i in shards:
            r = svc.searchers[i].query_phase(dict(body), shard_ord=i,
                                             stats_ctx=ctx)
            r.segments = []        # host-local only
            r.named_by_doc = {}
            out.append(r)
        return out

    def _local_fetch(self, index: str, body: dict, shard: int,
                     cands: List[tuple], g: dict) -> List[dict]:
        svc = self.node.indices[index]
        s = svc.searchers[shard]
        segs = (list(s.replica.segments) if s.replica is not None
                else list(s.engine.segments))
        result = ShardQueryResult(shard=shard, segments=segs)
        sel = [Candidate(shard, so, ld, sc, tuple(sv), tuple(rv))
               for so, ld, sc, sv, rv in cands]
        return s.fetch_phase(result, sel, dict(body),
                             stats_ctx=self._global_ctx(index, g))

    def search(self, index: str, body: dict,
               lane: str = "interactive") -> dict:
        """Distributed DFS_QUERY_THEN_FETCH across every member, reduced
        once on this node. The whole scatter/gather runs under ONE root
        span; every remote leg's span tree comes back on the RPC response
        and nests under the coordinator's phase span. Same deal for the
        flight recorder: the coordinator owns one timeline, every RPC
        carries it, and the remote legs' events graft back into it.
        A `timeout` in the body becomes the request deadline: every RPC
        and every local segment loop downstream derives its budget from
        it (utils/deadline.py). `lane` is the workload lane the SLIs and
        the remediation admission match run under (the wlm lane the REST
        facade derives on the single-node path)."""
        from ..obs import flight_recorder as _fr
        from ..utils.metrics import METRICS
        from ..utils.trace import TRACER
        from ..utils.wlm import PressureRejectedException
        try:
            dl = (_dl.current() or _dl.Deadline.from_body(body))
        except ValueError as e:
            raise ApiError(400, "parsing_exception", str(e))
        # remediation admission at the COORDINATOR boundary
        # (serving/remediator.py): an alert-named shape on the batch
        # lane sheds with 429 + Retry-After. A matching interactive
        # request is counted as deprioritized, but SLIs and insights
        # keep the ORIGIN lane — the distributed path has no scheduler
        # lanes to demote into, and relabeling would hide the burn
        # from the SLO that fired it. Inert while no action engaged.
        try:
            self._remediation().admit(body, lane)
        except PressureRejectedException as e:
            self._insights().record_rejection(
                body if isinstance(body, dict) else {}, lane,
                source="remediation")
            from ..rest.client import _rejected_429
            raise _rejected_429(e)
        token = None
        if _fr.RECORDER.enabled and not _fr.current():
            tl = _fr.RECORDER.start("dist.search", index=index,
                                    node=self.name)
            token = _fr.set_current(tl)
        # per-lane SLIs at the COORDINATOR boundary (the distributed
        # path never crosses Node.search): the same requests/errors
        # counters + latency sketch the SLO engine windows (obs/slo.py),
        # and the same query-insights fingerprinting — distributed
        # workloads aggregate under the identical shape identity a
        # single node derives (obs/insights.py)
        from ..obs import insights as _ins
        t0 = time.monotonic()
        obs, ins_token = _ins.begin(body if isinstance(body, dict)
                                    else {}, lane)
        ins_tl = _fr.current() if _fr.RECORDER.enabled else 0
        try:
            with _dl.scope(dl), \
                    TRACER.span("dist.search", index=index,
                                coordinator=self.name):
                if _fr.RECORDER.enabled and _fr.current():
                    _fr.RECORDER.record(_fr.current(), "dist.accept",
                                        index=index,
                                        coordinator=self.name)
                resp = self._search_traced(index, body)
        except BaseException as e:
            # client-side 4xx API errors are the caller's fault, not
            # lost availability (the Node.search contract)
            is_5xx = getattr(e, "status", 500) >= 500
            if is_5xx:
                METRICS.counter(f"search.lane.{lane}.errors").inc()
            _ins.finish(ins_token, obs, error=is_5xx,
                        timeline_id=ins_tl)
            raise
        finally:
            if token is not None:
                _fr.reset_current(token)
        METRICS.counter(f"search.lane.{lane}.requests").inc()
        took_ms = (time.monotonic() - t0) * 1000.0
        if METRICS.enabled:
            METRICS.histogram(f"search.lane.{lane}.latency_ms").record(
                took_ms)
        _ins.finish(ins_token, obs, latency_ms=took_ms,
                    timeline_id=ins_tl)
        return resp

    # ---------------- per-phase scatter with retry + failover ----------

    def _scatter_phase(self, op: str, plan: Dict[int, List[str]],
                       shards: List[int], rs: _RequestState,
                       failures: Dict[int, dict], run_local,
                       run_remote) -> Tuple[Dict[int, object],
                                            Dict[int, str]]:
        """Run one phase over `shards`: group by each shard's preferred
        live copy, fan every member group of the round out as one
        parallel leg (`utils/legs.py` — self-legs run locally, the rest
        RPC), JOIN, and on a member's terminal failure FAIL each of its
        shards OVER to the next copy in `plan` (mutated in place so
        later phases inherit the discovered topology). A shard with no
        copies left lands in `failures` with its per-shard reason.
        Round latency is the MAX of the member legs, not the SUM; the
        failover re-planning between rounds runs on THIS thread in
        sorted member order, so plan mutation and failure bookkeeping
        stay exactly as deterministic as the serial loop
        (`OPENSEARCH_TPU_LEGS=0`). Returns (per-shard outputs,
        per-shard serving member)."""
        from ..obs import flight_recorder as _fr
        from ..utils.metrics import METRICS
        outputs: Dict[int, object] = {}
        assigned: Dict[int, str] = {}
        pending = [s for s in shards if s not in failures]
        while pending:
            groups: Dict[str, List[int]] = {}
            for s in pending:
                groups.setdefault(plan[s][0], []).append(s)
            next_pending: List[int] = []
            members = sorted(groups)
            ls = _legs.LegSet(f"dist.{op}")
            for member in members:
                mshards = sorted(groups[member])

                def leg(member=member, mshards=mshards):
                    if rs.dl is not None and rs.dl.exhausted():
                        raise _dl.DeadlineExhausted(
                            f"[{op}] budget exhausted")
                    if member == self.name:
                        return run_local(mshards)
                    return run_remote(member, mshards)
                ls.add_leg(leg, name=member)
            deadline_hit = False
            for member, leg_out in zip(members, ls.join()):
                mshards = sorted(groups[member])
                err = leg_out.error
                if err is None:
                    res = leg_out.value
                    for s in mshards:
                        outputs[s] = res[s]
                        assigned[s] = member
                elif isinstance(err, (_dl.DeadlineExhausted,
                                      _legs.LegWedged)):
                    # terminal for the whole phase: this leg's shards
                    # fail with a timeout reason — within budget, never
                    # a transport-cap stall. Sibling legs that DID
                    # complete keep their results (the serial arm would
                    # simply never have attempted them), and no further
                    # failover round starts (below).
                    rs.timed_out = True
                    deadline_hit = True
                    for s in mshards:
                        failures.setdefault(s, {
                            "type": "timeout_exception",
                            "node": plan[s][0] if plan[s] else None,
                            "reason": "request budget exhausted"})
                elif isinstance(err, _ShardCallFailed):
                    for s in mshards:
                        plan[s] = [m for m in plan[s] if m != err.member]
                        if plan[s]:
                            rs.failovers += 1
                            METRICS.counter("dist.rpc.failover").inc()
                            if rs.tl:
                                _fr.RECORDER.record(
                                    rs.tl, "rpc.failover", op=op,
                                    shard=s, from_node=err.member,
                                    to_node=plan[s][0])
                            next_pending.append(s)
                        else:
                            METRICS.counter("dist.shard_failed").inc()
                            failures[s] = {"type": err.kind,
                                           "node": err.member,
                                           "attempts": err.attempts}
                else:
                    # genuine API/coordinator errors propagate exactly
                    # as they did from the serial loop (first in member
                    # order)
                    raise err
            if deadline_hit or (next_pending and rs.dl is not None
                                and rs.dl.exhausted()):
                rs.timed_out = True
                for s in next_pending:
                    failures.setdefault(s, {
                        "type": "timeout_exception",
                        "node": plan[s][0] if plan[s] else None,
                        "reason": "request budget exhausted"})
                return outputs, assigned
            pending = next_pending
        return outputs, assigned

    def _remote_runner(self, op: str, rs: _RequestState, build_payload,
                       extract):
        """Wrap an RPC phase leg: `_rpc_failsafe` for the wire, and a
        malformed/incomplete response converts to a member failure (the
        old `KeyError` handling) instead of a coordinator crash."""

        def run(member: str, shards: List[int]):
            r = self._rpc_failsafe(member, op, build_payload(shards), rs)
            try:
                out = extract(r, shards)
                if any(s not in out for s in shards):
                    raise KeyError("incomplete phase response")
            except Exception:
                self.member_fd.note_failure(member)
                raise _ShardCallFailed(member, "bad_response", 1)
            return out
        return run

    def _search_traced(self, index: str, body: dict) -> dict:
        from ..obs import flight_recorder as _fr
        from ..utils.metrics import METRICS
        from ..utils.trace import TRACER
        from ..search import fusion
        if fusion.is_hybrid_body(body):
            # hybrid retrieval at the DISTRIBUTED coordinator: each
            # sub-query runs the full DFS→scatter→reduce→fetch ladder
            # (replica failover, deadline propagation and all) and the
            # fused page is the same pure function of the ranked
            # sub-pages the single-node arm computes — byte-identical
            # across arms by construction (search/fusion.py)
            try:
                hq = fusion.parse_hybrid(body)
            except dsl.QueryParseError as e:
                raise ApiError(400, "parsing_exception", str(e))
            tok = _fd_snap.set(frozenset(self.member_fd.deprioritized()))
            try:
                return fusion.run_hybrid(
                    body, lambda sub: self._search_traced(index, sub),
                    q=hq)
            finally:
                _fd_snap.reset(tok)
        t0 = time.monotonic()
        agg_nodes = self._check_supported(body)
        svc = self.node.indices.get(index)
        if svc is None:
            raise ApiError(404, "index_not_found_exception",
                           f"no such index [{index}]")
        n_shards = svc.meta.num_shards
        copies = self.copies.get(
            index, {s: [self.name] for s in range(n_shards)})
        # per-request copy preference: configured order with
        # detector-deprioritized members demoted; the scatter phases
        # mutate the plan as they discover dead copies, so later phases
        # inherit the topology the earlier ones learned.  Inside a
        # hybrid fan-out, every sub-retrieval plans from the snapshot
        # taken at the hybrid entry (see _fd_snap) rather than a
        # mid-request read that would race with sibling legs.
        snap = _fd_snap.get()
        depri = set(snap) if snap is not None \
            else self.member_fd.deprioritized()
        plan = {s: order_copies(copies.get(s, [self.name]), depri)
                for s in range(n_shards)}
        rs = _RequestState(self.retry_policy, _dl.current(),
                           _fr.current() if _fr.RECORDER.enabled else 0)
        failures: Dict[int, dict] = {}
        all_shards = list(range(n_shards))

        # --- phase 1: DFS (one copy of every shard's collection stats)
        with TRACER.span("dist.dfs", shards=n_shards), \
                METRICS.timer("dist.dfs"):
            dfs_out, _dfs_assigned = self._scatter_phase(
                "dfs", plan, all_shards, rs, failures,
                run_local=lambda sh: self._local_dfs(index, body, sh),
                run_remote=self._remote_runner(
                    "dfs", rs,
                    lambda sh: {"index": index, "body": body,
                                "shards": sh},
                    lambda r, sh: {s: rec for s, rec in
                                   _unb64(r["recs"]).items()
                                   if s in set(sh)}))
        if any(rec.get("named") for rec in dfs_out.values()):
            raise ApiError(400, "illegal_argument_exception",
                           "named queries (_name) are not supported "
                           "on a distributed index")
        g = _merge_dfs([dfs_out[s] for s in sorted(dfs_out)])

        # --- phase 2: QUERY the same copies with pinned global stats
        with TRACER.span("dist.query", shards=len(dfs_out)), \
                METRICS.timer("dist.query"):
            q_out, q_assigned = self._scatter_phase(
                "query_phase", plan, sorted(dfs_out), rs, failures,
                run_local=lambda sh: {
                    r.shard: r
                    for r in self._local_query(index, body, g, sh)},
                run_remote=self._remote_runner(
                    "query_phase", rs,
                    lambda sh: {"index": index, "body": body,
                                "g": _b64(g), "shards": sh},
                    lambda r, sh: {sr.shard: sr
                                   for sr in _unb64(r["results"])
                                   if sr.shard in sh}))
        merged = [q_out[s] for s in sorted(q_out)]

        with TRACER.span("dist.reduce", shards=len(merged)):
            reduced = reduce_shard_results(merged, body,
                                           agg_nodes=agg_nodes)

        # --- phase 3: FETCH winners from the copy that ran their query
        # phase (doc coordinates are copy-local: fetch retries in place
        # but never fails over — a copy lost between phases fails its
        # shard honestly, reference query-and-fetch affinity)
        by_shard: Dict[int, List[Candidate]] = {}
        for c in reduced["selected"]:
            by_shard.setdefault(c.shard, []).append(c)
        hits_by_key: Dict[Tuple, dict] = {}
        with TRACER.span("dist.fetch", shards=len(by_shard)), \
                METRICS.timer("dist.fetch"):
            # one leg per shard (fetch has no failover — retries in
            # place, copy affinity): legs overlap the per-copy fetch
            # RPCs, the per-shard failure bookkeeping below runs on
            # this thread in shard order
            fetch_items = sorted(by_shard.items())
            fls = _legs.LegSet("dist.fetch")
            for s_id, sel in fetch_items:
                owner = q_assigned.get(s_id, self.name)

                def fleg(s_id=s_id, sel=sel, owner=owner):
                    if owner == self.name:
                        sr = self.node.indices[index].searchers[s_id]
                        segs = (list(sr.replica.segments)
                                if sr.replica is not None
                                else list(sr.engine.segments))
                        res = ShardQueryResult(shard=s_id, segments=segs)
                        return sr.fetch_phase(
                            res, sel, dict(body),
                            stats_ctx=self._global_ctx(index, g))
                    cands = [(c.seg_ord, c.local_doc, c.score,
                              list(c.sort_values),
                              list(c.raw_sort_values))
                             for c in sel]
                    r = self._rpc_failsafe(
                        owner, "fetch_phase",
                        {"index": index, "body": body,
                         "shard": s_id, "cands": _b64(cands),
                         "g": _b64(g)}, rs)
                    return _unb64(r["hits"])
                fls.add_leg(fleg, name=str(s_id))
            for (s_id, sel), leg_out in zip(fetch_items, fls.join()):
                owner = q_assigned.get(s_id, self.name)
                err = leg_out.error
                remote = owner != self.name
                if err is None:
                    fetched = leg_out.value
                elif remote and isinstance(err, (_dl.DeadlineExhausted,
                                                 _legs.LegWedged)):
                    rs.timed_out = True
                    failures[s_id] = {
                        "type": "timeout_exception", "node": owner,
                        "reason": "request budget exhausted"}
                    fetched = []
                elif remote and isinstance(err,
                                           (_ShardCallFailed, KeyError)):
                    # the copy died BETWEEN query and fetch: this
                    # shard's winners can no longer be hydrated —
                    # report the shard failed instead of silently
                    # returning fewer hits
                    METRICS.counter("dist.shard_failed").inc()
                    failures[s_id] = {
                        "type": getattr(err, "kind",
                                        "node_unreachable"),
                        "node": owner,
                        "attempts": getattr(err, "attempts", 1)}
                    fetched = []
                else:
                    # local-leg errors propagate exactly as the serial
                    # (un-tried) local branch did
                    raise err
                for c, h in zip(sel, fetched):
                    hits_by_key[(c.shard, c.seg_ord, c.local_doc)] = h
        hits = [hits_by_key[(c.shard, c.seg_ord, c.local_doc)]
                for c in reduced["selected"]
                if (c.shard, c.seg_ord, c.local_doc) in hits_by_key]
        for h in hits:
            h["_index"] = index

        track = body.get("track_total_hits", True)
        total, relation = reduced["total"], reduced.get("total_rel", "eq")
        if track is not True and track is not False:
            track_n = int(track)
            if total > track_n:
                total, relation = track_n, "gte"
        timed_out = rs.timed_out or any(
            getattr(r, "timed_out", False) for r in merged)
        terminated_early = any(getattr(r, "terminated_early", False)
                               for r in merged)
        failed_list = [{"shard": s, "node": f.get("node"),
                        "reason": {k: v for k, v in f.items()
                                   if k != "node"}}
                       for s, f in sorted(failures.items())]
        if body.get("allow_partial_search_results", True) is False \
                and (failed_list or timed_out):
            # reference parity: partial results refused -> the whole
            # request fails (SearchPhaseExecutionException shape)
            raise ApiError(
                503, "search_phase_execution_exception",
                f"{len(failed_list)} shard failure(s)"
                f"{' and a timeout' if timed_out else ''} with "
                f"allow_partial_search_results=false")
        resp = {
            "took": int((time.monotonic() - t0) * 1000),
            "timed_out": timed_out,
            "_shards": {"total": n_shards,
                        "successful": n_shards - len(failed_list),
                        "skipped": 0, "failed": len(failed_list),
                        **({"failures": failed_list}
                           if failed_list else {})},
            "hits": {"total": {"value": total, "relation": relation},
                     "max_score": (reduced["max_score"]
                                   if reduced["max_score"] != float("-inf")
                                   else None),
                     "hits": hits},
        }
        if terminated_early:
            resp["terminated_early"] = True
        if reduced["aggs"]:
            resp["aggregations"] = reduced["aggs"]
        return resp

    # ---------------- fleet observability federation ----------------
    #
    # `GET /_cluster/stats`, `_nodes/stats`, `_nodes/{id}/hot_threads`
    # and `_nodes/stats/history` fan out over the same `/_internal` RPC
    # plane the search phases ride (docs/OBSERVABILITY.md "fleet"):
    # counters SUM cluster-wide, gauges roll up PER NODE, and DDSketch
    # histograms merge bin-wise (`utils/metrics.merge_sketches`) so
    # fleet p50/p95/p99 come from ONE merged sketch — never from
    # averaged per-node percentiles. Scrape failures degrade honestly:
    # an unreachable member contributes a per-node `failed` entry and
    # the `_nodes` rollup counts it; the coordinator never stalls past
    # the scrape cap (deadline-ctx rides the scrape like any RPC).

    def _obs_reg(self):
        if self.obs_registry is not None:
            return self.obs_registry
        from ..utils.metrics import METRICS
        return METRICS

    def _handle_obs(self, op: str, body: dict) -> dict:
        """Serving side of a fleet scrape (`/_internal/{stats,node_stats,
        hot_threads,history}`)."""
        if op == "stats":
            return {"node": self.name,
                    "wire": self._obs_reg().to_wire(),
                    "indices": self.client.indices_summary()}
        if op == "node_stats":
            local = self.client.nodes_stats()
            block = local["nodes"].get(self.node.node_name) or {}
            return {"node": self.name, "stats": block}
        if op == "indexing":
            # this node's `indexing.*` registry slice in wire form — the
            # coordinator sums counters/gauges and MERGES the sketches
            # (obs/ingest_obs.merge_parts), so fleet refresh-to-visible
            # percentiles come from one merged sketch
            from ..obs import ingest_obs as _iobs
            return {"node": self.name,
                    "parts": _iobs.local_parts(self._obs_reg())}
        if op == "hot_threads":
            from ..obs.hot_threads import hot_threads as _ht
            return {"node": self.name, "result": _ht(
                node_name=self.name,
                snapshots=int(body.get("snapshots", 3)),
                interval_s=float(body.get("interval_ms", 20)) / 1000.0,
                ignore_idle=bool(body.get("ignore_idle", True)),
                as_json=bool(body.get("as_json", False)))}
        if op == "insights":
            w = body.get("window_s")
            return {"node": self.name,
                    "wire": self._insights().to_wire(
                        window_s=float(w) if w is not None else None)}
        if op == "remediation":
            return {"node": self.name, "status": "ok",
                    **self._remediation().status()}
        # history
        from ..obs.timeseries import SAMPLER
        return {"node": self.name,
                "history": SAMPLER.history(
                    str(body.get("metric") or ""),
                    float(body.get("window_s", 60.0)))}

    def _insights(self):
        if self.insights_engine is not None:
            return self.insights_engine
        from ..obs.insights import INSIGHTS
        return INSIGHTS

    def _remediation(self):
        if self.remediation_engine is not None:
            return self.remediation_engine
        from ..serving.remediator import REMEDIATOR
        return REMEDIATOR

    def _scrape_timeout_s(self) -> float:
        dl = _dl.current()
        cap = min(_RPC_TIMEOUT_CAP_S, _SCRAPE_CAP_S)
        return dl.rpc_timeout_s(cap) if dl is not None else cap

    def _scrape(self, op: str, payload: dict,
                members: Optional[List[str]] = None) -> Dict[str, tuple]:
        """Fan one obs RPC out CONCURRENTLY; returns member ->
        ("ok", result) or ("failed", reason). The self leg never crosses
        the wire. Remote legs run on per-member threads carrying the
        caller's context (deadline/trace/obs ctx ride each scrape), so
        the whole fan-out is bounded by ONE scrape timeout — k wedged
        members cost max(cap), not k*cap (utils/legs.py)."""
        from ..utils.metrics import METRICS
        want = sorted(members if members is not None else self.members)
        timeout_s = self._scrape_timeout_s()

        def leg(member: str) -> tuple:
            if member == self.name:
                return ("ok", self._handle_obs(op, payload))
            try:
                return ("ok", self._rpc(member, op, payload,
                                        timeout_s=timeout_s))
            except (urllib.error.URLError, OSError, TimeoutError) as e:
                METRICS.counter("dist.scrape.failed").inc()
                return ("failed", f"{type(e).__name__}: {e}"[:200])

        ls = _legs.LegSet(f"dist.scrape.{op}")
        for member in want:
            ls.add_leg(lambda m=member: leg(m), name=member)
        out: Dict[str, tuple] = {}
        for member, leg_out in zip(want, ls.join(timeout_s=timeout_s
                                                 + _legs.JOIN_GRACE_S)):
            if leg_out.error is not None:
                out[member] = ("failed",
                               f"{type(leg_out.error).__name__}: "
                               f"{leg_out.error}"[:200])
            else:
                out[member] = leg_out.value
        return out

    def _resolve_member(self, node_id: Optional[str]) -> List[str]:
        """`_nodes/{id}/...` member filter. `_all`/`_local`/None keep
        reference semantics; an unknown id is a 404, never a silent
        coordinator-only answer."""
        if node_id in (None, "_all"):
            return sorted(self.members)
        if node_id == "_local":
            return [self.name]
        if node_id in self.members:
            return [node_id]
        raise ApiError(404, "resource_not_found_exception",
                       f"no such node [{node_id}]")

    def cluster_stats(self) -> dict:
        """`GET /_cluster/stats`: the fleet rollup. Counters sum, gauges
        stay per-node, histograms merge into true fleet percentiles,
        index totals sum over exactly the members that answered."""
        from ..utils.metrics import merge_sketches, sketch_snapshot
        scraped = self._scrape("stats", {})
        nodes: Dict[str, dict] = {}
        counters: Dict[str, float] = {}
        hist_wires: Dict[str, list] = {}
        indices = {"docs": 0, "store_in_bytes": 0, "segments": 0}
        ok = 0
        for member, (status, res) in scraped.items():
            if status != "ok":
                nodes[member] = {"status": "failed", "error": res}
                continue
            ok += 1
            wire = res.get("wire") or {}
            nodes[member] = {"status": "ok",
                             "gauges": wire.get("gauges", {}),
                             "counters": wire.get("counters", {}),
                             "indices": res.get("indices", {})}
            for k, v in (wire.get("counters") or {}).items():
                counters[k] = counters.get(k, 0) + v
            for k, w in (wire.get("histograms") or {}).items():
                hist_wires.setdefault(k, []).append(w)
            for k in indices:
                indices[k] += int((res.get("indices") or {}).get(k, 0))
        merged = {k: merge_sketches(ws)
                  for k, ws in sorted(hist_wires.items())}
        return {
            "cluster_name": self.node.metadata.cluster_name,
            "coordinator": self.name,
            "_nodes": {"total": len(scraped), "successful": ok,
                       "failed": len(scraped) - ok},
            "nodes": nodes,
            "indices": indices,
            "counters": dict(sorted(counters.items())),
            # fleet percentiles FROM MERGED SKETCHES (the per-node
            # sketches are also returned so a reader can re-derive)
            "percentiles": {k: sketch_snapshot(w)
                            for k, w in merged.items()},
            "histograms": merged,
        }

    def indexing_stats(self) -> dict:
        """`GET /_nodes/stats/indexing` federated: scrape every member's
        `indexing.*` wire parts, fold them (counters and gauges sum —
        the fleet writer buffer is the sum of node buffers; DDSketch
        histograms merge bin-wise), then assemble the SAME block shape
        one node serves (obs/ingest_obs.assemble_block). Percentiles are
        computed from the merged sketch, never averaged. Unreachable
        members degrade to `failed` entries in `_nodes`."""
        from ..obs import ingest_obs as _iobs
        scraped = self._scrape("indexing", {})
        parts = []
        nodes = {}
        ok = 0
        for member, (status, res) in scraped.items():
            if status == "ok":
                ok += 1
                parts.append(res.get("parts") or {})
                nodes[member] = {"status": "ok"}
            else:
                nodes[member] = {"status": "failed", "error": res}
        block = _iobs.assemble_block(_iobs.merge_parts(parts), nodes=ok)
        return {"cluster_name": self.node.metadata.cluster_name,
                "coordinator": self.name,
                "_nodes": {"total": len(scraped), "successful": ok,
                           "failed": len(scraped) - ok},
                "nodes": nodes,
                "indexing": block}

    def nodes_stats_federated(self, node_id: Optional[str] = None
                              ) -> dict:
        """`GET /_nodes[/{id}]/stats` with node fan-out: each targeted
        member's full per-node stats block under its cluster member
        name; unreachable members degrade to `{"failed": ...}` entries,
        an unknown id is a 404 (never a silent whole-fleet answer)."""
        scraped = self._scrape("node_stats", {},
                               self._resolve_member(node_id))
        nodes = {}
        ok = 0
        for member, (status, res) in scraped.items():
            if status == "ok":
                ok += 1
                nodes[member] = res.get("stats") or {}
            else:
                nodes[member] = {"failed": res}
        return {"cluster_name": self.node.metadata.cluster_name,
                "_nodes": {"total": len(scraped), "successful": ok,
                           "failed": len(scraped) - ok},
                "nodes": nodes}

    def hot_threads_federated(self, node_id: Optional[str] = None,
                              snapshots: int = 3,
                              interval_ms: float = 20.0,
                              ignore_idle: bool = True,
                              as_json: bool = False):
        """`GET /_nodes[/{id}]/hot_threads` across the cluster: per-node
        sections (each member samples ITS OWN process — before this,
        the coordinator silently sampled only itself), unreachable
        members as explicit failed sections."""
        members = self._resolve_member(node_id)
        payload = {"snapshots": int(snapshots),
                   "interval_ms": float(interval_ms),
                   "ignore_idle": bool(ignore_idle),
                   "as_json": bool(as_json)}
        scraped = self._scrape("hot_threads", payload, members)
        if as_json:
            return {"nodes": {
                m: ({"threads": res.get("result")} if status == "ok"
                    else {"failed": res})
                for m, (status, res) in scraped.items()}}
        parts = []
        for m, (status, res) in scraped.items():
            if status == "ok":
                parts.append(str(res.get("result")))
            else:
                parts.append(f"::: {{{m}}}\n   <hot_threads scrape "
                             f"failed: {res}>\n")
        return "".join(parts)

    def history_federated(self, metric: str, window_s: float = 60.0,
                          node_id: Optional[str] = None) -> dict:
        """`GET /_nodes[/{id}]/stats/history`: each member's local
        time-series window for one metric (obs/timeseries.py)."""
        members = self._resolve_member(node_id)
        scraped = self._scrape(
            "history", {"metric": metric, "window_s": float(window_s)},
            members)
        nodes = {}
        ok = 0
        for m, (status, res) in scraped.items():
            if status == "ok":
                ok += 1
                nodes[m] = res.get("history") or {}
            else:
                nodes[m] = {"failed": res}
        return {"metric": metric, "window_s": float(window_s),
                "_nodes": {"total": len(scraped), "successful": ok,
                           "failed": len(scraped) - ok},
                "nodes": nodes}

    def top_queries_federated(self, by: str = "latency", n: int = 10,
                              window_s: Optional[float] = None,
                              node_id: Optional[str] = None) -> dict:
        """`GET /_insights/top_queries` on a cluster: every member's
        heavy-hitter sketch wire merges through the commutative
        space-saving merge (`obs/insights.py merge_wires`), so the
        fleet's top-N is computed from ONE merged summary — never from
        concatenated per-node top lists (which under-rank a shape that
        is #11 everywhere but #1 fleet-wide). Unreachable members
        degrade to per-node `failed` entries, the merge covers whoever
        answered."""
        from ..obs import insights as _ins
        if by not in _ins.TOP_BY:
            raise ApiError(400, "illegal_argument_exception",
                           f"unknown top_queries ranking [{by}] "
                           f"(one of {_ins.TOP_BY})")
        payload = ({"window_s": float(window_s)}
                   if window_s is not None else {})
        scraped = self._scrape("insights", payload,
                               self._resolve_member(node_id))
        wires = []
        nodes: Dict[str, dict] = {}
        ok = 0
        for member, (status, res) in scraped.items():
            if status == "ok":
                ok += 1
                wires.append(res.get("wire") or {})
                nodes[member] = {"status": "ok"}
            else:
                nodes[member] = {"status": "failed", "error": res}
        cap = self._insights().capacity
        n = max(int(n), 0)     # the QueryInsights.top clamp, mirrored
        if window_s is not None:
            merged = _ins.merge_windowed_wires(wires, cap,
                                               float(window_s))
            top = sorted(merged["entries"],
                         key=_ins.QueryInsights._rank_key(by))[:n]
        else:
            merged = _ins.merge_wires(wires, cap)
            top = sorted((_ins._derived(d) for d in merged["entries"]),
                         key=_ins.QueryInsights._rank_key(by))[:n]
        return {"by": by, "n": int(n),
                **({"window_s": float(window_s)}
                   if window_s is not None else {}),
                "capacity": cap,
                "total_records": merged["total_records"],
                "_nodes": {"total": len(scraped), "successful": ok,
                           "failed": len(scraped) - ok},
                "nodes": nodes,
                "top_queries": top}

    def remediation_federated(self, node_id: Optional[str] = None
                              ) -> dict:
        """`GET /_remediation` on a cluster: every member's live action
        table + engage/release counters, fanned out on the `/_internal`
        plane with the standard unreachable-member degradation — the
        operator's one-stop "what is the fleet doing to itself right
        now" pane."""
        scraped = self._scrape("remediation", {},
                               self._resolve_member(node_id))
        nodes: Dict[str, dict] = {}
        ok = 0
        active_total = 0
        for member, (status, res) in scraped.items():
            if status == "ok":
                ok += 1
                nodes[member] = {k: v for k, v in res.items()
                                 if k != "node"}
                active_total += len(res.get("active") or [])
            else:
                nodes[member] = {"status": "failed", "error": res}
        return {"_nodes": {"total": len(scraped), "successful": ok,
                           "failed": len(scraped) - ok},
                "active_actions_total": active_total,
                "nodes": nodes}

    # ---------------- lifecycle + stats ----------------

    def resilience_stats(self) -> dict:
        """This node's failure-domain view: member detector state + the
        retry policy in force (the counter rollup lives in
        `_nodes/stats` "resilience" and `/_metrics`)."""
        p = self.retry_policy
        return {"member_detector": self.member_fd.stats(),
                "retry_policy": {
                    "same_member_retries": p.same_member_retries,
                    "budget": p.budget,
                    "base_backoff_s": p.base_backoff_s,
                    "max_backoff_s": p.max_backoff_s,
                    "storm_n": p.storm_n},
                "rpc_timeout_cap_s": _RPC_TIMEOUT_CAP_S}

    def stop(self) -> None:
        self.server.stop()
