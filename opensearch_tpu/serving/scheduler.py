"""Serving scheduler: cross-request dynamic batching with deadline-aware
flush and priority lanes (docs/SERVING.md).

The runtime's device programs batch over the QUERY axis (mesh
`try_msearch` groups, fastpath `msearch_batched` kernel grids), but only
queries arriving inside one `_msearch` body ever shared a launch —
concurrent independent searches from `ThreadingHTTPServer` threads each
paid their own dispatch and serialized on the chip. This scheduler sits
between the REST layer and `MeshSearchService`: eligible searches enqueue
into a bounded two-lane queue, and a single dispatcher thread flushes the
pending set as ONE batched program invocation when either `max_batch`
requests are waiting or the oldest has waited `max_wait_us` (whichever
first). Per-request futures carry results, errors and timeouts back to
the submitting HTTP threads.

Contracts:

- **Bit-identical results.** A flushed batch rides the exact query-axis
  batching `_msearch` already uses (`MeshSearchService.try_msearch`,
  `executor.msearch_batched`); per-query scoring is independent of batch
  composition (pow2 query padding, per-row f32 accumulation, per-query
  top-k merge), so a coalesced search serves the same pages, scores and
  tie-breaks as a direct one — the f32 tie-serve contract from
  docs/FASTPATH.md is untouched. `SchedulerConfig.oracle` re-runs every
  coalesced body through
  the direct path on the dispatcher thread and counts mismatches.
- **Graceful degradation.** Non-coalescable shapes bypass the queue
  unchanged (`accepts`); a closed scheduler, an entry still queued at
  the request timeout (wedged dispatcher), or a batch execution error
  falls back to direct per-request execution (an entry already claimed
  into an in-flight batch is waited out, not duplicated) — the scheduler
  can only ever make an eligible request *batched*, never make it fail.
- **Cancellation.** A cancelled `utils/tasks.py` task is dropped from the
  pending set before launch: `Task.on_cancel` wakes the scheduler, which
  resolves the entry with `TaskCancelledException` without dispatching it.
- **Admission.** The queue is bounded (`queue_cap`); a full queue rejects
  with `PressureRejectedException` (HTTP 429) and is counted by
  `SearchBackpressureService` — concurrency converts to backpressure, not
  unbounded growth.

Lanes: requests carry a lane from their `utils/wlm.py` workload group
("interactive" default; groups configured with `lane: "batch"`, and
scroll-initiating searches, ride the batch lane). At flush time the
interactive lane preempts the batch lane: interactive entries fill the
batch first, batch/scroll entries only take the leftover slots.

All waiting uses `threading.Condition` / `threading.Event` — no sleep
polling (oslint OSL503, docs/STATIC_ANALYSIS.md).

**Pipelined dispatch** (this PR): the dispatch path is split into an
explicit LAUNCH stage and a FETCH/RENDER stage connected by
`search/launch.py` LaunchHandles. The dispatcher thread now only
assembles and *launches* (program invocation under
`MeshSearchService._dispatch_lock`, released before any sync); completed
launches enter a bounded in-flight window and a completion worker thread
performs the device sync, oracle re-check, response rendering and future
resolution — so host assembly of batch N+1 overlaps device execution of
batch N. `SchedulerConfig.pipeline_depth` bounds the window
(default 2); depth 1 is byte-for-byte
the old synchronous dispatcher (and the `JAX_PLATFORMS=cpu` oracle
baseline). Degradation ladders extend to the new stage: a wedged
completion worker abandons the claimed entry to direct execution on the
request thread after a second `request_timeout_s`, and a task cancelled
after launch but before fetch resolves immediately (the batch's result
for it is discarded). Telemetry: `serving.inflight_depth` gauge,
`serving.launch_to_fetch` histogram, and a launch/fetch stage overlap
ratio in `_nodes/stats` "serving" -> "pipeline" and `/_metrics`.
"""

from __future__ import annotations

import copy as _copy
import json as _json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from ..obs import flight_recorder as _fr
from ..utils.metrics import METRICS, MetricsRegistry
from ..utils.tasks import TaskCancelledException
from ..utils.wlm import PressureRejectedException

LANES = ("interactive", "batch")

# body keys MeshSearchService._eligible statically declines — queueing
# these shapes would add latency for a guaranteed host-loop outcome, so
# they bypass the scheduler unchanged (the decline still happens at the
# same place it does today, with the same attribution).
# `knn` is NOT in this list (ISSUE 15): pure-knn bodies are first-class
# scheduler citizens — they enqueue, ride the lanes/admission/429 path
# (so the remediator can shed vector floods), and coalesce through the
# vmapped batched-knn program (executor._launch_knn_segment)
_BYPASS_KEYS = ("rescore", "min_score", "profile", "collapse",
                "suggest", "search_after", "highlight", "script_fields",
                # budgeted bodies need the deadline-AWARE executor: only
                # the host shard loop stops between segment programs
                # (terminate_after) / checks the deadline — the batched
                # mesh/kernel launches are deadline-blind, so a `timeout`
                # body coalesced into a batch could blow its budget
                # inside one launch with no partial-results exit. The
                # entry.wait_s derivation below still serves requests
                # whose deadline arrives AMBIENTLY (hop-propagated
                # deadline_ctx, no body timeout — ROADMAP item 2's
                # per-node schedulers)
                "terminate_after", "timeout")

# entry states (transitions under the scheduler condition lock)
_QUEUED, _CLAIMED, _DONE, _ABANDONED = "queued", "claimed", "done", "abandoned"


class SchedulerConfig:
    """Tuning knobs (see docs/SERVING.md for the latency/throughput
    trade-off each one moves)."""

    def __init__(self, max_batch: int = 32,
                 max_wait_us: int = 1000,
                 queue_cap: int = 256,
                 oracle: bool = False,
                 kernel_batching: bool = True,
                 request_timeout_s: float = 30.0,
                 idle_timeout_s: float = 5.0,
                 pipeline_depth: int = 2):
        self.max_batch = int(max_batch)
        self.max_wait_us = int(max_wait_us)
        self.queue_cap = int(queue_cap)
        self.oracle = bool(oracle)
        # also coalesce mesh-declined / mesh-less bodies through the
        # fastpath's grouped kernel launches (executor.msearch_batched)
        self.kernel_batching = bool(kernel_batching)
        self.request_timeout_s = float(request_timeout_s)
        self.idle_timeout_s = float(idle_timeout_s)
        # bounded in-flight window for pipelined dispatch: at most this
        # many launched-but-unfetched batches, so the device queue can't
        # grow without bound. Depth 1 == the synchronous dispatcher the
        # scheduler shipped with (launch+fetch on one thread) — the
        # JAX_PLATFORMS=cpu oracle baseline for pipeline parity.
        self.pipeline_depth = int(pipeline_depth)
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_wait_us < 0:
            raise ValueError("max_wait_us must be >= 0")
        if self.queue_cap < 1:
            raise ValueError("queue_cap must be >= 1")
        if self.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")


class _Pending:
    __slots__ = ("name", "svc", "body", "lane", "task", "enq", "done",
                 "resp", "error", "state", "tl", "wait_s")

    def __init__(self, name: str, svc, body: dict, lane: str, task):
        self.name = name
        self.svc = svc
        self.body = body
        self.lane = lane
        self.task = task
        self.enq = time.monotonic()
        self.done = threading.Event()
        self.resp = None            # response dict, or None (-> host loop)
        self.error: Optional[BaseException] = None
        self.state = _QUEUED
        # flight-recorder timeline of the submitting request: the
        # dispatcher/completion threads have no ambient timeline, so the
        # id rides the entry explicitly (0 = recorder disabled)
        self.tl = 0
        # scheduler deadline, derived from the request's remaining
        # budget at enqueue (deadline ladder, docs/RESILIENCE.md); None
        # = no ambient deadline, wait the configured request timeout
        self.wait_s: Optional[float] = None

    def _stage(self, stage) -> None:
        """Mark the live serving stage on the request's task (surfaced by
        `_tasks`; None = left the scheduler); no-op for task-less
        entries."""
        t = self.task
        if t is not None and hasattr(t, "set_stage"):
            t.set_stage(stage)


class _StageMeter:
    """Interval-union accounting for the launch and fetch stages: per-kind
    busy seconds plus the union wall during which ANY stage was active.
    overlap = launch_s + fetch_s - union_s is the wall the two stages ran
    concurrently — the host-side witness that device execution (the fetch
    stage blocks on it) overlapped host assembly. At pipeline depth 1 the
    stages share one thread, so the overlap is identically zero."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active = 0
        self._mark = 0.0
        self.stage_s = {"launch": 0.0, "fetch": 0.0}
        self.union_s = 0.0

    @contextmanager
    def stage(self, kind: str):
        t0 = time.monotonic()
        with self._lock:
            if self._active == 0:
                self._mark = t0
            self._active += 1
        try:
            yield
        finally:
            t1 = time.monotonic()
            with self._lock:
                self._active -= 1
                self.stage_s[kind] += t1 - t0
                if self._active == 0:
                    self.union_s += t1 - self._mark
                ratio = self._ratio_locked()
            METRICS.gauge("serving.overlap_ratio").set(round(ratio, 4))

    def _ratio_locked(self) -> float:
        total = self.stage_s["launch"] + self.stage_s["fetch"]
        if self.union_s <= 0.0:
            return 0.0
        return max(total - self.union_s, 0.0) / self.union_s

    def snapshot(self) -> dict:
        with self._lock:
            total = self.stage_s["launch"] + self.stage_s["fetch"]
            return {
                "launch_s": round(self.stage_s["launch"], 4),
                "fetch_s": round(self.stage_s["fetch"], 4),
                "union_s": round(self.union_s, 4),
                "overlap_s": round(max(total - self.union_s, 0.0), 4),
                "overlap_ratio": round(self._ratio_locked(), 4),
            }


class _InFlight:
    """One launched batch parked in the in-flight window: per-(index,
    service) groups, each holding its claimed entries and the launch
    handles the completion worker will fetch."""

    __slots__ = ("groups", "launched_at")

    def __init__(self, groups: list):
        # [(name, svc, entries, bodies, handles-or-None, launch_error)]
        self.groups = groups
        self.launched_at = time.monotonic()

    def unresolved(self):
        for _name, _svc, entries, _bodies, _handles, _err in self.groups:
            for e in entries:
                if not e.done.is_set():
                    yield e


class ServingScheduler:
    """One per Node. `execute()` is the only entry point the search path
    uses; everything else is dispatcher machinery and telemetry."""

    def __init__(self, node, config: Optional[SchedulerConfig] = None,
                 enabled: Optional[bool] = None):
        self.node = node
        self.config = config or SchedulerConfig()
        if enabled is None:
            flag = os.environ.get("OPENSEARCH_TPU_SCHED")
            if flag is not None:
                enabled = flag not in ("", "0")
            else:
                # default: on whenever there is a device batching substrate
                # worth coalescing for (the SPMD mesh); single-chip nodes
                # opt in with OPENSEARCH_TPU_SCHED=1 (kernel batching)
                enabled = node.mesh_service is not None
        self.enabled = bool(enabled)
        # the one condition every enqueue/flush/close handshake rides;
        # its only committed downstream acquisition is the metrics
        # registry (lock_order.json) — never call out to RPC/device
        # work while holding it (OSL702)
        self._cond = threading.Condition()
        self._lanes: Dict[str, deque] = {lane: deque() for lane in LANES}
        self._pending = 0
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # pipelined dispatch: launched-but-unfetched batches (bounded by
        # config.pipeline_depth; the head entry stays in the deque while
        # the completion worker fetches it, so the window counts every
        # batch the device still owes results for)
        self._inflight: deque = deque()
        self._cthread: Optional[threading.Thread] = None
        self._meter = _StageMeter()
        self._inflight_peak = 0
        self.launched_batches = 0
        self.completed_batches = 0
        self.cancelled_inflight = 0     # cancelled after launch, pre-fetch
        self.completion_abandoned = 0   # wedged completion -> ran direct
        # counters (mutated under self._cond; mirrored into METRICS)
        self.submitted = 0
        self.batched_served = 0     # resolved with a batched response
        self.declined = 0           # resolved None -> host loop
        self.bypassed = 0           # accepts() said no -> direct path
        self.rejected = 0           # queue full -> 429
        self.cancelled_dropped = 0  # dropped before launch
        self.direct_fallbacks = 0   # degraded mode: ran direct
        self.batch_errors = 0
        self.flushes = 0
        self.flush_reasons = {"size": 0, "deadline": 0, "drain": 0}
        self.lane_flushed = {lane: 0 for lane in LANES}
        self.oracle_checks = 0
        self.oracle_mismatches = 0
        self.last_oracle_mismatch: Optional[str] = None
        # per-instance histogram mirror: the process-global METRICS
        # registry feeds /_metrics, but THIS node's `_nodes/stats` block
        # must not blend in a co-resident node's flushes (remote-cluster
        # peers, multi-node tests share the process)
        self._local = MetricsRegistry()

    # ---------------- eligibility ----------------

    def accepts(self, body) -> bool:
        """Cheap coalescability screen. Permissive by design: anything it
        lets through still goes through the mesh/fastpath's own
        eligibility and falls back to the host loop on decline; this only
        spares statically-hopeless shapes the queue wait."""
        if not isinstance(body, dict):
            return False
        for k in _BYPASS_KEYS:
            if body.get(k) is None:
                continue
            if k == "timeout":
                # only a LIVE budget forces the host loop; the reference
                # no-timeout sentinel (-1 -> no deadline) keeps batching
                from ..utils.deadline import parse_timeout_s
                try:
                    if parse_timeout_s(body["timeout"]) is None:
                        continue
                except ValueError:
                    pass             # junk -> host loop raises the 400
            return False
        if body.get("explain") == "device_plan":
            # the device-plan cost view needs the requesting thread's own
            # cost accumulator (obs/query_cost.py) — a coalesced launch
            # on the dispatcher thread can't attribute per-request
            return False
        q = body.get("query")
        if q is not None and not isinstance(q, dict):
            return False
        return True

    # ---------------- admission state ----------------

    def _effective_cap(self) -> int:
        """The live admission bound: queue_cap, contracted by the
        remediation actuator's admission factor while a
        tighten_admission action holds (never below 1)."""
        cap = self.config.queue_cap
        rem = getattr(self.node, "remediation", None)
        if rem is not None and rem.tightened:
            cap = max(1, int(cap * rem.queue_factor()))
        return cap

    def _retry_after_s(self, depth: int) -> float:
        """The honest `Retry-After` hint for a queue-full 429, derived
        from the admission state the client just hit: the estimated
        drain time of the current queue (batches needed x the flush
        deadline), floored so a zero-wait config still asks for a
        beat of backoff."""
        per_flush_s = max(self.config.max_wait_us / 1e6, 0.01)
        batches = max((depth + self.config.max_batch - 1)
                      // self.config.max_batch, 1)
        return max(batches * per_flush_s, 0.05)

    # ---------------- request side ----------------

    def execute(self, name: str, svc, body: dict, task=None,
                lane: str = "interactive"):
        """Coalesce one eligible search into the next flushed batch.
        Returns the batched response dict, or None when the batch path
        declined the body (caller runs the host shard loop — identical to
        a direct mesh decline). Raises PressureRejectedException when the
        queue is full and TaskCancelledException when the request's task
        was cancelled before launch."""
        if lane not in self._lanes:
            lane = "interactive"
        entry = _Pending(name, svc, body, lane, task)
        if _fr.RECORDER.enabled:
            entry.tl = _fr.current()
        from ..utils import deadline as _ddl
        _dl = _ddl.current()
        if _dl is not None:
            # the scheduler's own deadline derives from what is LEFT of
            # the request budget at enqueue — queue wait spends from the
            # same clock as everything downstream
            entry.wait_s = max(min(self.config.request_timeout_s,
                                   _dl.remaining_s()), 0.0)
        # ONE critical section for closed-check, admission, dispatcher
        # liveness and enqueue: the dispatcher's idle-exit decision runs
        # under the same condition, so an entry can never land in the
        # queue with no dispatcher alive and none restarted
        rejected_depth = None
        closed = False
        # admission cap: the configured bound, contracted while a
        # remediation tighten_admission action is engaged
        # (serving/remediator.py) — 429s fire earlier under active
        # remediation, and relax to exactly queue_cap on release
        cap = self._effective_cap()
        with self._cond:
            if self._closed:
                self.direct_fallbacks += 1
                METRICS.counter("serving.direct_fallbacks").inc()
                closed = True
            elif self._pending >= cap:
                self.rejected += 1
                METRICS.counter("serving.rejected").inc()
                # per-lane mirror: ONE consistent rejection name across
                # every admission layer (wlm, scheduler, remediation) —
                # the SLO engine's rejection-rate objectives and the
                # remediation loop both window serving.lane.*.rejected
                METRICS.counter(f"serving.lane.{lane}.rejected").inc()
                self.node.search_backpressure.note_queue_rejection()
                rejected_depth = self._pending
            else:
                if not self._dispatcher_alive():
                    self._start_dispatcher()
                self.submitted += 1
                METRICS.counter("serving.submitted").inc()
                METRICS.counter(f"serving.lane.{lane}.submitted").inc()
                self._lanes[lane].append(entry)
                self._pending += 1
                METRICS.gauge("serving.queue_depth").set(self._pending)
                entry._stage("queued")
                if _fr.RECORDER.enabled and entry.tl:
                    _fr.RECORDER.record(entry.tl, "sched.enqueue",
                                        lane=lane, depth=self._pending)
                self._cond.notify_all()
        if rejected_depth is not None:
            # attribute the 429 to the request's query shape: the
            # insights engine counts rejections per fingerprint, the
            # admission-threshold remediation input (obs/insights.py)
            from ..obs import insights as _ins
            _ins.note_rejection_source("scheduler")
            # event + burst detection OUTSIDE the scheduler lock: a burst
            # trigger freezes a dump bundle, and that scan must not stall
            # every other submit/flush/cancel on _cond
            if _fr.RECORDER.enabled:
                if entry.tl:
                    _fr.RECORDER.record(entry.tl, "sched.reject",
                                        pending=rejected_depth,
                                        cap=cap)
                _fr.RECORDER.note_rejection(entry.tl)
            raise PressureRejectedException(
                f"serving scheduler queue full "
                f"({rejected_depth}/{cap} pending); "
                f"rejecting search",
                retry_after_s=self._retry_after_s(rejected_depth),
                source="scheduler")
        if closed:
            if _fr.RECORDER.enabled and entry.tl:
                _fr.RECORDER.record(entry.tl, "sched.degrade",
                                    why="closed")
            return self._direct(name, svc, body)
        if task is not None and hasattr(task, "on_cancel"):
            # wake + drop the entry the moment its task is cancelled (the
            # flush assembly re-checks as a backstop)
            task.on_cancel(lambda _t, e=entry: self._drop_cancelled(e))
        return self._await(entry)

    def _await(self, entry: _Pending):
        wait1 = (entry.wait_s if entry.wait_s is not None
                 else self.config.request_timeout_s)
        deadline_cut = entry.wait_s is not None \
            and entry.wait_s < self.config.request_timeout_s
        if not entry.done.wait(wait1):
            with self._cond:
                if entry.state == _QUEUED:
                    # scheduler wedged with the entry still queued: pull it
                    # and degrade to direct execution on this thread
                    try:
                        self._lanes[entry.lane].remove(entry)
                        self._pending -= 1
                        METRICS.gauge("serving.queue_depth").set(
                            self._pending)
                        self._cond.notify_all()
                    except ValueError:
                        pass
                    entry.state = _ABANDONED
                    self.direct_fallbacks += 1
                    METRICS.counter("serving.direct_fallbacks").inc()
            if entry.state == _ABANDONED:
                if deadline_cut:
                    # the REQUEST's budget (shorter than the scheduler
                    # timeout) ran out while queued — not a wedge, no
                    # dump: degrade to direct execution, which the
                    # executor's own deadline check turns into an
                    # immediate honest timed_out partial page
                    if _fr.RECORDER.enabled and entry.tl:
                        _fr.RECORDER.record(
                            entry.tl, "sched.degrade",
                            why="request_deadline",
                            waited_ms=round(
                                (time.monotonic() - entry.enq) * 1000.0,
                                3))
                    entry._stage(None)
                    return self._direct(entry.name, entry.svc, entry.body)
                # the request missed its deadline while STILL QUEUED — the
                # dispatcher is wedged or starved. Freeze the timeline
                # before degrading: this is exactly the after-the-fact
                # forensic moment the flight recorder exists for
                if _fr.RECORDER.enabled and entry.tl:
                    _fr.RECORDER.record(
                        entry.tl, "sched.degrade", why="deadline_miss",
                        waited_ms=round(
                            (time.monotonic() - entry.enq) * 1000.0, 3))
                    _fr.RECORDER.trigger(
                        "deadline_miss", [entry.tl],
                        note=f"entry still queued after "
                             f"{self.config.request_timeout_s}s")
                entry._stage(None)
                return self._direct(entry.name, entry.svc, entry.body)
            # claimed: the batch is in flight on the device. Duplicating
            # it immediately would be wasteful, so give the completion
            # stage one more request_timeout — but a WEDGED completion
            # worker (hung fetch) must not hold the request hostage:
            # abandon the entry and run direct on this thread (the batch
            # result for it is discarded by the state guard).
            if not entry.done.wait(self.config.request_timeout_s):
                with self._cond:
                    if entry.state == _CLAIMED:
                        entry.state = _ABANDONED
                        self.direct_fallbacks += 1
                        self.completion_abandoned += 1
                        METRICS.counter("serving.direct_fallbacks").inc()
                        METRICS.counter(
                            "serving.completion_abandoned").inc()
                if entry.state == _ABANDONED:
                    # launched but never fetched: the completion stage is
                    # wedged. Dump the timeline (it already holds the
                    # flush's batch peers and the launch boundary) before
                    # running direct on this thread
                    if _fr.RECORDER.enabled and entry.tl:
                        _fr.RECORDER.record(
                            entry.tl, "sched.degrade",
                            why="completion_wedge",
                            waited_ms=round(
                                (time.monotonic() - entry.enq) * 1000.0,
                                3))
                        _fr.RECORDER.trigger(
                            "completion_wedge", [entry.tl],
                            note=f"claimed entry unresolved after "
                                 f"2x{self.config.request_timeout_s}s")
                    entry._stage(None)
                    return self._direct(entry.name, entry.svc, entry.body)
                entry.done.wait()     # resolved racing with our timeout
        if entry.error is not None:
            raise entry.error
        return entry.resp

    def _drop_cancelled(self, entry: _Pending) -> None:
        with self._cond:
            if entry.state == _CLAIMED and not entry.done.is_set():
                # already launched, not yet fetched: the device work can't
                # be recalled, but the caller need not wait for it — mark
                # the entry resolved-with-cancellation now; the completion
                # stage's state guard discards the batch result for it
                entry.state = _DONE
                entry.error = TaskCancelledException(
                    f"task [{getattr(entry.task, 'id', '?')}] cancelled "
                    f"after batch launch, before fetch: "
                    f"{getattr(entry.task, 'cancel_reason', None)}")
                self.cancelled_inflight += 1
                METRICS.counter("serving.cancelled_inflight").inc()
                if _fr.RECORDER.enabled and entry.tl:
                    _fr.RECORDER.record(entry.tl, "sched.cancel",
                                        where="inflight")
                entry._stage(None)
                entry.done.set()
                return
            if entry.state != _QUEUED:
                return
            try:
                self._lanes[entry.lane].remove(entry)
                self._pending -= 1
                METRICS.gauge("serving.queue_depth").set(self._pending)
                self._cond.notify_all()      # wake drain() waiters
            except ValueError:
                return
            self._resolve_cancelled(entry)

    def _resolve_cancelled(self, entry: _Pending) -> None:
        entry.state = _DONE
        entry.error = TaskCancelledException(
            f"task [{getattr(entry.task, 'id', '?')}] cancelled while "
            f"queued for batch dispatch: "
            f"{getattr(entry.task, 'cancel_reason', None)}")
        self.cancelled_dropped += 1
        METRICS.counter("serving.cancelled_dropped").inc()
        if _fr.RECORDER.enabled and entry.tl:
            _fr.RECORDER.record(entry.tl, "sched.cancel", where="queued")
        entry._stage(None)
        entry.done.set()

    # ---------------- dispatcher side ----------------

    def _dispatcher_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _start_dispatcher(self) -> None:
        self._thread = threading.Thread(target=self._loop,
                                        name="ostpu-serving-dispatcher",
                                        daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        me = threading.current_thread()
        while True:
            with self._cond:
                # idle wait: exit after idle_timeout so test suites that
                # spin up hundreds of Nodes don't accumulate parked
                # threads; submit() restarts the dispatcher lazily
                while self._pending == 0 and not self._closed:
                    if not self._cond.wait(self.config.idle_timeout_s) \
                            and self._pending == 0:
                        if self._thread is me:
                            self._thread = None
                        return
                if self._closed and self._pending == 0:
                    return
                reason = self._wait_flush()
                if self._pending == 0:
                    continue
                # in-flight window backpressure: launching past the
                # window would let the device queue grow without bound —
                # wait for the completion worker to retire a batch (the
                # queue keeps admitting, and batching, meanwhile)
                while len(self._inflight) >= self.config.pipeline_depth \
                        and not self._closed:
                    self._cond.wait(self.config.idle_timeout_s)
                batch = self._assemble(reason)
            if not batch:
                continue
            try:
                if self.config.pipeline_depth <= 1:
                    # depth 1 == the pre-pipeline dispatcher: launch +
                    # fetch + render synchronously on this thread
                    with self._meter.stage("launch"):
                        self._dispatch(batch)
                else:
                    with self._meter.stage("launch"):
                        item = self._launch_stage(batch)
                    self._enqueue_inflight(item)
            except BaseException:           # noqa: BLE001
                # never strand claimed entries: whatever killed the
                # dispatch, every waiter degrades to the host loop
                for e in batch:
                    if not e.done.is_set():
                        e.resp = None
                        e.state = _DONE
                        e.done.set()
                raise

    def _wait_flush(self) -> str:
        """Block (under the cond) until the flush policy fires: size
        (max_batch pending) or deadline (oldest waited max_wait_us)."""
        max_wait_s = self.config.max_wait_us / 1e6
        while True:
            if self._closed:
                return "drain"
            if self._pending >= self.config.max_batch:
                return "size"
            heads = [self._lanes[lane][0].enq for lane in LANES
                     if self._lanes[lane]]
            oldest = min(heads) if heads else None
            if oldest is None:
                return "deadline"     # emptied while we slept
            remaining = max_wait_s - (time.monotonic() - oldest)
            if remaining <= 0:
                return "deadline"
            self._cond.wait(remaining)

    def _assemble(self, reason: str) -> List[_Pending]:
        """Pop up to max_batch entries — interactive lane first (FIFO
        within a lane, batch/scroll lane fills the leftover slots) — and
        drop entries whose task was cancelled while queued. One slot is
        reserved for the batch lane whenever it has waiters: preemption
        means the interactive lane goes first, not that sustained
        interactive saturation starves scroll traffic into its request
        timeout."""
        batch: List[_Pending] = []
        for lane in LANES:                  # interactive preempts batch
            cap = self.config.max_batch
            if lane == "interactive" and self._lanes["batch"] and cap > 1:
                cap -= 1                    # starvation guard
            q = self._lanes[lane]
            while q and len(batch) < cap:
                entry = q.popleft()
                self._pending -= 1
                if entry.task is not None and \
                        getattr(entry.task, "cancelled", False):
                    self._resolve_cancelled(entry)
                    continue
                entry.state = _CLAIMED
                batch.append(entry)
                self.lane_flushed[lane] += 1
                METRICS.counter(f"serving.lane.{lane}.flushed").inc()
        METRICS.gauge("serving.queue_depth").set(self._pending)
        self._cond.notify_all()          # wake drain() waiters
        if batch:
            self.flushes += 1
            self.flush_reasons[reason] = \
                self.flush_reasons.get(reason, 0) + 1
            METRICS.counter(f"serving.flush.{reason}").inc()
            METRICS.histogram("serving.batch_size").record(len(batch))
            self._local.histogram("serving.batch_size").record(len(batch))
            now = time.monotonic()
            for e in batch:
                wait_ms = (now - e.enq) * 1000.0
                METRICS.histogram("serving.queue_wait").record(wait_ms)
                self._local.histogram("serving.queue_wait").record(wait_ms)
            if _fr.RECORDER.enabled:
                # batch peers: every timeline in this flush carries the
                # full co-batched set, so a dump of ONE wedged request
                # names the requests that shared its launch
                peers = [e.tl for e in batch if e.tl]
                for e in batch:
                    if e.tl:
                        _fr.RECORDER.record(
                            e.tl, "sched.flush", reason=reason,
                            size=len(batch), lane=e.lane,
                            queue_wait_ms=round(
                                (now - e.enq) * 1000.0, 3),
                            peers=[p for p in peers if p != e.tl])
        return batch

    def _dispatch(self, batch: List[_Pending]) -> None:
        """Depth-1 synchronous dispatch: run the flushed batch grouped by
        index and hand every entry its result on this thread. Never
        raises: a failed group degrades its entries to the host loop
        (resp None). Stage marks (launched/fetching/rendering) and the
        per-entry launch/fetch boundary events mirror the pipelined
        path's, so `_tasks` and timelines read identically at any depth."""
        for (name, svc, entries, bodies) in self._group(batch):
            try:
                handles = self._launch_group(name, svc, bodies)
                err = False
            except Exception:                       # noqa: BLE001
                handles = None
                err = True
            for e in entries:
                if e.state == _CLAIMED:   # not cancelled/abandoned since
                    e._stage("launched")
            self._record_launch(entries, handles, err)
            if err:
                with self._cond:
                    self.batch_errors += 1
                METRICS.counter("serving.batch_errors").inc()
                resps = [None] * len(entries)
            else:
                for e in entries:
                    if e.state == _CLAIMED:
                        e._stage("fetching")
                try:
                    resps = self._finish_group(name, svc, bodies, handles)
                except Exception:                   # noqa: BLE001
                    with self._cond:
                        self.batch_errors += 1
                    METRICS.counter("serving.batch_errors").inc()
                    resps = [None] * len(entries)
            for e in entries:
                if e.state == _CLAIMED:
                    e._stage("rendering")
            if self.config.oracle:
                self._oracle_check(name, svc, entries, resps)
            self._resolve_entries(entries, resps)

    def _record_launch(self, entries: List[_Pending], handles,
                       err: bool) -> None:
        """Per-entry launch-boundary events. The dispatcher thread has no
        ambient timeline, so the ids ride the entries; `handle.info`
        carries the mesh's launch forensics (dispatch-lock wait, new
        program compiles)."""
        if not _fr.RECORDER.enabled:
            return
        fields: dict = {"path": "none"}
        if handles is not None:
            mesh_handle, kernel_handle = handles
            h = mesh_handle if mesh_handle is not None else kernel_handle
            if h is not None:
                fields["path"] = ("mesh" if mesh_handle is not None
                                  else "kernel")
                if getattr(h, "info", None):
                    fields.update(h.info)
        if err:
            fields["error"] = True
        for e in entries:
            if e.tl:
                _fr.RECORDER.record(e.tl, "sched.launch", **fields)

    @staticmethod
    def _group(batch: List[_Pending]) -> list:
        """[(name, svc, entries, bodies)] grouped by (name, service
        identity), not name alone: two entries can hold DIFFERENT
        IndexService snapshots for one name (index deleted + recreated
        between their enqueues) and each must be served from its own
        service, like the direct path would.

        Bodies are top-level COPIES: the batch paths insert top-level
        keys (`_mesh_declined`, `_index_name`) and iterate the dict, and
        an entry abandoned to direct execution (completion wedge) has its
        ORIGINAL body concurrently read by the request thread — sharing
        the dict would let a late fetch mutate it mid-iteration. Inner
        structures are read-only on both sides and stay shared."""
        groups: Dict[tuple, List[_Pending]] = {}
        for e in batch:
            groups.setdefault((e.name, id(e.svc)), []).append(e)
        return [(name, entries[0].svc, entries,
                 [dict(e.body) if isinstance(e.body, dict) else e.body
                  for e in entries])
                for (name, _sid), entries in groups.items()]

    def _resolve_entries(self, entries: List[_Pending],
                         resps: list) -> None:
        """Hand each claimed entry its result. The state guard makes
        resolution race-free against the in-flight degradation paths: an
        entry cancelled after launch or abandoned to direct execution by
        a wedged completion stage is NOT overwritten — its batch result
        is discarded."""
        served = declined = 0
        for e, r in zip(entries, resps):
            with self._cond:
                if e.state != _CLAIMED:
                    continue
                e.state = _DONE
                if r is not None:
                    self.batched_served += 1
                    served += 1
                else:
                    self.declined += 1
                    declined += 1
            e.resp = r
            if _fr.RECORDER.enabled and e.tl:
                _fr.RECORDER.record(e.tl, "sched.resolve",
                                    served=r is not None)
            e._stage(None)
            e.done.set()
        METRICS.counter("serving.batched_served").inc(served)
        METRICS.counter("serving.declined").inc(declined)

    # ---------------- pipelined dispatch ----------------

    def _launch_group(self, name: str, svc, bodies: List[dict]) -> tuple:
        """LAUNCH stage for one (index, service) group: the SPMD mesh's
        program invocations (multi-shard), or — mesh-less nodes — the
        fastpath's grouped kernel launches. Returns unfetched handles;
        no device sync happens here (oslint OSL504)."""
        node = self.node
        mesh_handle = None
        kernel_handle = None
        if node.mesh_service is not None:
            mesh_handle = node.mesh_service.launch_msearch(name, svc,
                                                           bodies)
        elif self.config.kernel_batching and len(bodies) >= 2:
            from ..search.executor import launch_msearch_batched
            kernel_handle = launch_msearch_batched(svc.searchers, bodies,
                                                   index_name=name)
        handle = mesh_handle if mesh_handle is not None else kernel_handle
        if handle is not None:
            # batch workspace tenant: the pinned per-request top-k output
            # buffers (score f32 + doc i32 per window slot) the device
            # owes while this batch sits in the in-flight window;
            # released at the handle's deferred sync (or the handle's GC
            # — a wedged/abandoned batch must not pin the stamp).
            # ADVISORY (uncharged): the programs are already launched,
            # so a breaker trip here could only waste the device work by
            # degrading the whole batch to the host loop
            from ..obs.hbm_ledger import LEDGER
            slots = sum(int(b.get("from", 0)) + int(b.get("size", 10))
                        for b in bodies if isinstance(b, dict))
            handle.ws_alloc = LEDGER.register(
                "batch_workspace", slots * 8, owner=handle, charge=False,
                label=f"sched-batch[{name}]x{len(bodies)}")
        return (mesh_handle, kernel_handle)

    def _finish_group(self, name: str, svc, bodies: List[dict],
                      handles: tuple) -> list:
        """FETCH/RENDER stage for one group: sync the mesh launch, then
        coalesce the mesh-declined remainder through the fastpath's
        grouped kernel launches (their eligibility is only known once the
        mesh results are back, so that stage launches-and-fetches here).
        Entries still None take the host loop on their own request
        threads — which also parallelizes the host-side fallback work
        instead of serializing it here."""
        mesh_handle, kernel_handle = handles
        resps: List[Optional[dict]] = [None] * len(bodies)
        if mesh_handle is not None:
            mesh = mesh_handle.fetch()
            if mesh is not None:
                resps = list(mesh)
        todo = [i for i, r in enumerate(resps) if r is None]
        if kernel_handle is not None:
            batched = kernel_handle.fetch()
            if batched is not None:
                for i, r in zip(todo, batched):
                    if resps[i] is None:
                        resps[i] = r
        elif mesh_handle is not None and self.config.kernel_batching \
                and len(todo) >= 2:
            # kernel batching only when there is something to coalesce: a
            # LONE mesh-declined body must take exactly the scheduler-off
            # path (host loop, incl. its shard-view/pruned attribution)
            # — coalescing may change execution only when it fuses
            from ..search.executor import msearch_batched
            batched = msearch_batched(svc.searchers,
                                      [bodies[i] for i in todo],
                                      index_name=name)
            if batched is not None:
                for i, r in zip(todo, batched):
                    if resps[i] is None:
                        resps[i] = r
        for h in (mesh_handle, kernel_handle):
            ms = h.launch_to_fetch_ms() if h is not None else None
            if ms is not None:
                # scheduler-owned handles only: this is the pipeline's
                # deferred-sync window, not a general fetch timer
                METRICS.histogram("serving.launch_to_fetch").record(ms)
                self._local.histogram("serving.launch_to_fetch").record(ms)
        return resps

    def _launch_stage(self, batch: List[_Pending]) -> _InFlight:
        """Dispatcher side of pipelined dispatch: launch every group's
        programs and return the in-flight record. A group whose launch
        raises is recorded as errored — its entries degrade to the host
        loop at completion (never here: the dispatcher must get back to
        `_wait_flush` immediately)."""
        groups = []
        for (name, svc, entries, bodies) in self._group(batch):
            try:
                handles = self._launch_group(name, svc, bodies)
                err = False
            except Exception:                       # noqa: BLE001
                handles = None
                err = True
            for e in entries:
                if e.state == _CLAIMED:   # not cancelled/abandoned since
                    e._stage("launched")
            self._record_launch(entries, handles, err)
            groups.append((name, svc, entries, bodies, handles, err))
        return _InFlight(groups)

    def _enqueue_inflight(self, item: _InFlight) -> None:
        with self._cond:
            self._inflight.append(item)
            self.launched_batches += 1
            depth = len(self._inflight)
            self._inflight_peak = max(self._inflight_peak, depth)
            METRICS.counter("serving.pipeline.launched").inc()
            METRICS.gauge("serving.inflight_depth").set(depth)
            if not self._completion_alive():
                self._start_completion()
            self._cond.notify_all()

    def _completion_alive(self) -> bool:
        return self._cthread is not None and self._cthread.is_alive()

    def _start_completion(self) -> None:
        self._cthread = threading.Thread(
            target=self._completion_loop,
            name="ostpu-serving-completion", daemon=True)
        self._cthread.start()

    def _completion_loop(self) -> None:
        """Completion worker: retire in-flight batches FIFO — device
        sync, oracle re-check, response rendering, future resolution.
        The head batch stays in the window while it is being fetched, so
        the dispatcher's backpressure bound counts it."""
        me = threading.current_thread()
        while True:
            with self._cond:
                while not self._inflight and not self._closed:
                    if not self._cond.wait(self.config.idle_timeout_s) \
                            and not self._inflight:
                        if self._cthread is me:
                            self._cthread = None
                        return
                if not self._inflight:
                    return          # closed and drained
                item = self._inflight[0]
            try:
                with self._meter.stage("fetch"):
                    self._complete(item)
            finally:
                # never strand entries, whatever killed the completion
                for e in item.unresolved():
                    with self._cond:
                        if e.state != _CLAIMED:
                            continue
                        e.state = _DONE
                    e.resp = None
                    e.done.set()
                with self._cond:
                    if self._inflight and self._inflight[0] is item:
                        self._inflight.popleft()
                    self.completed_batches += 1
                    METRICS.counter("serving.pipeline.completed").inc()
                    METRICS.gauge("serving.inflight_depth").set(
                        len(self._inflight))
                    self._cond.notify_all()     # wake the dispatcher

    def _complete(self, item: _InFlight) -> None:
        """Fetch + render + resolve one in-flight batch. Never raises for
        per-group failures: an errored group degrades its entries to the
        host loop (resp None), exactly like the synchronous dispatcher."""
        from ..cluster import faults as _faults
        if _faults.enabled():
            # chaos site: slow-fetch / completion-stage fault injection
            # (cluster/faults.py; the degradation ladder above this —
            # completion wedge -> request-thread direct — is what the
            # injected stall exercises)
            _faults.on_sched_complete(self.node.node_name)
        for (name, svc, entries, bodies, handles, err) in item.groups:
            if err:
                resps = [None] * len(entries)
                with self._cond:
                    self.batch_errors += 1
                METRICS.counter("serving.batch_errors").inc()
            else:
                for e in entries:
                    if e.state == _CLAIMED:
                        e._stage("fetching")
                t_fetch = time.monotonic()
                try:
                    resps = self._finish_group(name, svc, bodies, handles)
                except Exception:                   # noqa: BLE001
                    with self._cond:
                        self.batch_errors += 1
                    METRICS.counter("serving.batch_errors").inc()
                    resps = [None] * len(entries)
                if _fr.RECORDER.enabled:
                    fetch_ms = round(
                        (time.monotonic() - t_fetch) * 1000.0, 3)
                    for e in entries:
                        if e.tl:
                            _fr.RECORDER.record(e.tl, "sched.fetch",
                                                fetch_ms=fetch_ms)
            for e in entries:
                if e.state == _CLAIMED:
                    e._stage("rendering")
            if self.config.oracle:
                # pipelined batches re-run against the direct path too:
                # pipeline on/off must be byte-identical
                self._oracle_check(name, svc, entries, resps)
            self._resolve_entries(entries, resps)

    # ---------------- degraded / oracle paths ----------------

    def _direct(self, name: str, svc, body: dict):
        """Direct per-request execution — exactly what Node.search does
        with the scheduler off (mesh attempt; host loop stays with the
        caller, which treats None as a decline)."""
        if self.node.mesh_service is not None:
            return self.node.mesh_service.try_search(name, svc, body)
        return None

    def _oracle_reference(self, name: str, svc, body: dict):
        """The direct-execution equivalent of a SERVED batched body:
        the mesh when it serves the shape, else a batch-of-one kernel
        launch (probing the grouped kernel path's batch-size
        invariance) — mirroring the launch+fetch stages _dispatch
        composes."""
        if self.node.mesh_service is not None:
            direct = self.node.mesh_service.try_search(name, svc, body)
            if direct is not None:
                return direct
        from ..search.executor import msearch_batched
        single = msearch_batched(svc.searchers, [body], index_name=name)
        return single[0] if single is not None else None

    @staticmethod
    def _normalize(resp) -> Optional[str]:
        if resp is None:
            return None
        out = {k: v for k, v in resp.items() if k != "took"}
        return _json.dumps(out, sort_keys=True, default=repr)

    def _oracle_check(self, name: str, svc, entries: List[_Pending],
                      resps: list) -> None:
        """Run every body through the direct path too and compare (modulo
        wall-clock `took`). Dispatch counters run twice in this mode — it
        exists to prove the identical-results contract, not to serve."""
        for e, r in zip(entries, resps):
            if r is None:
                # declined (or error-degraded): the caller's host loop
                # serves it — nothing BATCHED was produced to verify
                continue
            oracle_body = _copy.deepcopy(e.body)
            oracle_body.pop("_mesh_declined", None)
            try:
                direct = self._oracle_reference(name, svc, oracle_body)
                match = self._normalize(r) == self._normalize(direct)
            except Exception:                       # noqa: BLE001
                match = False
            with self._cond:
                self.oracle_checks += 1
                if not match:
                    self.oracle_mismatches += 1
                    self.last_oracle_mismatch = (
                        f"index [{name}] body "
                        f"{_json.dumps(e.body, default=repr)[:400]}: "
                        f"batched != direct")
            METRICS.counter("serving.oracle_checks").inc()
            if not match:
                METRICS.counter("serving.oracle_mismatches").inc()
                # a coalesced result diverging from direct execution is
                # the worst anomaly this subsystem can produce — freeze
                # the request's full journal for the postmortem
                if _fr.RECORDER.enabled and e.tl:
                    _fr.RECORDER.record(e.tl, "sched.oracle_mismatch",
                                        index=name)
                    _fr.RECORDER.trigger("oracle_mismatch", [e.tl],
                                         note=f"index [{name}]: "
                                              f"batched != direct")

    # ---------------- lifecycle + stats ----------------

    def drain(self, timeout: float = 5.0) -> bool:
        """Block until the pending queue is empty WITHOUT closing the
        scheduler (a transport shutting down must not end the Node-wide
        scheduler's life — another transport, or the dict API, keeps
        coalescing). Returns False when the timeout expired first."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._pending > 0 or self._inflight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def close(self, drain: bool = True) -> None:
        """Stop the dispatcher and the completion worker. With drain=True
        pending entries are flushed one last time and in-flight launches
        retired; without it they degrade to direct execution via the
        request-thread timeout path."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            t = self._thread
            ct = self._cthread
        if drain:
            if t is not None:
                t.join(timeout=5.0)
            if ct is not None:
                ct.join(timeout=5.0)

    def stats(self) -> dict:
        with self._cond:
            depth = self._pending
            out = {
                "enabled": self.enabled,
                "queue_depth": depth,
                "queue_cap": self.config.queue_cap,
                "effective_queue_cap": self._effective_cap(),
                "max_batch": self.config.max_batch,
                "max_wait_us": self.config.max_wait_us,
                "submitted": self.submitted,
                "batched_served": self.batched_served,
                "declined": self.declined,
                "bypassed": self.bypassed,
                "rejected": self.rejected,
                "cancelled_dropped": self.cancelled_dropped,
                "direct_fallbacks": self.direct_fallbacks,
                "batch_errors": self.batch_errors,
                "flushes": self.flushes,
                "flush_reasons": dict(self.flush_reasons),
                "lanes": {lane: {"flushed": self.lane_flushed[lane]}
                          for lane in LANES},
                "oracle": {"enabled": self.config.oracle,
                           "checks": self.oracle_checks,
                           "mismatches": self.oracle_mismatches},
                "pipeline": {
                    "depth": self.config.pipeline_depth,
                    "inflight": len(self._inflight),
                    "inflight_peak": self._inflight_peak,
                    "launched_batches": self.launched_batches,
                    "completed_batches": self.completed_batches,
                    "cancelled_inflight": self.cancelled_inflight,
                    "completion_abandoned": self.completion_abandoned,
                },
            }
        out["pipeline"].update(self._meter.snapshot())
        out["batch_size"] = self._local.percentiles("serving.batch_size")
        out["queue_wait_ms"] = self._local.percentiles("serving.queue_wait")
        out["launch_to_fetch_ms"] = self._local.percentiles(
            "serving.launch_to_fetch")
        return out

    def note_bypass(self) -> None:
        with self._cond:
            self.bypassed += 1
        METRICS.counter("serving.bypassed").inc()
