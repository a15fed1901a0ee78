"""Flight recorder: a per-request black-box event journal.

PR 3's telemetry answers "how fast is the system on average"; this module
answers "what exactly happened to THAT request". Every search gets a
*timeline* — an ordered sequence of structured events from REST accept
through wlm lane classification, scheduler enqueue/flush (with batch
peers), launch (mesh vs fastpath, dispatch-lock wait, new program
compiles), fetch, fastpath ladder rungs, and every degradation
(deadline miss, completion wedge, cancel, 429, direct fallback) — so a
single bad request under serving load is reconstructable after the fact.
Reference analog: the forensic half of OpenSearch's `_tasks` +
`_nodes/hot_threads` introspection, with the event-journal discipline of
an aircraft flight recorder: always on, fixed cost, frozen on anomaly.

Design constraints (the hot path is the serving scheduler's dispatcher
and the fastpath ladder):

- **Lock-light ring.** `record()` is one atomic sequence bump
  (`itertools.count` — a C-level single-op under the GIL) plus one slot
  store of a fully-built tuple. No lock, no allocation beyond the event
  tuple itself; concurrent writers can interleave but never tear a slot
  (readers see either the old tuple or the new one) and never lose an
  event while the ring is within capacity (each sequence number owns a
  distinct slot until wraparound).
- **Lazy payloads.** Emission sites in serving/search hot paths guard
  with `if RECORDER.enabled:` BEFORE building the event's field dict —
  the disabled path is one attribute read. oslint OSL505 enforces the
  guard (and the monotonic-timestamp discipline) statically.
- **Monotonic time.** Events carry `time.monotonic()` only; dumps
  convert to wall clock through a single (wall, mono) anchor captured at
  construction, so a stepped wall clock can reorder nothing.

Timelines are keyed to the existing trace context: `Node.search` stamps
the root span id onto the timeline, and `cluster/distnode.py` carries
`(node, timeline)` on its `/_internal` RPCs so the remote side's events
come back on the response and graft into the coordinator's timeline —
one stitched cross-node story per distributed search.

On an anomaly trigger — deadline miss, completion wedge, scheduler
rejection burst, oracle mismatch, slowlog threshold, or a manual
`POST /_flight_recorder/dump` — the recorder freezes the relevant
timelines into a JSON dump bundle (bounded count, bounded timelines per
bundle) retrievable via `GET /_flight_recorder`.
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence

from ..utils.metrics import METRICS

__all__ = ["FlightRecorder", "RECORDER", "current", "set_current",
           "reset_current"]

# ambient timeline id for the executing request (0 = none). Propagates
# into pool workers via the context-carrying submit in utils/threadpool;
# the serving scheduler's own threads carry ids explicitly on entries.
_current_tl: contextvars.ContextVar = contextvars.ContextVar(
    "opensearch_tpu_timeline", default=0)


def current() -> int:
    return _current_tl.get()


def set_current(tl: int):
    return _current_tl.set(tl)


def reset_current(token) -> None:
    _current_tl.reset(token)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return repr(v)


class FlightRecorder:
    """Fixed-size event ring + bounded timeline registry + dump store.

    One per process (module singleton `RECORDER`), like `utils/trace.py`
    TRACER and `utils/metrics.py` METRICS — one node per process is the
    deployment reality, and multi-node tests sharing a process simply
    share the black box (events carry the node via timeline meta)."""

    # anomaly reasons with a cooldown (storm-shaped triggers must not
    # flood the dump store); wedges/deadline misses always dump
    _COOLDOWN_REASONS = ("rejection_burst", "slowlog", "oracle_mismatch",
                         "retry_storm", "slo_burn", "refresh_stall")

    def __init__(self, capacity: int = 4096, max_dumps: int = 16,
                 enabled: bool = True,
                 max_dump_timelines: int = 32,
                 max_timeline_events: int = 512,
                 cooldown_s: float = 0.25,
                 burst_n: int = 8, burst_window_s: float = 1.0):
        self.capacity = int(capacity)
        if self.capacity < 16:
            raise ValueError("flight recorder capacity must be >= 16")
        self.max_dumps = int(max_dumps)
        self.enabled = bool(enabled)
        self.max_dump_timelines = int(max_dump_timelines)
        self.max_timeline_events = int(max_timeline_events)
        self.cooldown_s = float(cooldown_s)
        self.burst_n = int(burst_n)
        self.burst_window_s = float(burst_window_s)
        # wall-clock anchor: events carry monotonic time only; dumps
        # convert through this single pair (plain timestamp, never
        # differenced against monotonic readings from another clock)
        self._anchor_wall = time.time()
        self._anchor_mono = time.monotonic()
        # the ring: slot i%capacity holds (seq, tl, t_mono, kind, fields)
        self._slots: List[Optional[tuple]] = [None] * self.capacity
        self._seq = itertools.count()
        # timeline ids + bounded metadata (allocation is once per request
        # — a small lock here is fine; only record() must stay lock-free)
        self._tl_ids = itertools.count(1)
        self._timelines: "OrderedDict[int, dict]" = OrderedDict()
        self._meta_lock = threading.Lock()
        self._meta_cap = max(self.capacity // 4, 256)
        # dump store + trigger bookkeeping
        self._dump_lock = threading.Lock()
        self._dumps: deque = deque(maxlen=self.max_dumps)
        self._dump_ids = itertools.count(1)
        self._last_trigger: Dict[str, float] = {}
        self.trigger_counts: Dict[str, int] = {}
        self.suppressed_triggers = 0
        self.timelines_started = 0
        # 429-burst detection window: (mono, tl) of recent rejections.
        # Own lock (NOT _dump_lock: trigger() takes that) — concurrent
        # rejecting schedulers must not race the window scan
        self._rej_lock = threading.Lock()
        self._rejections: deque = deque(maxlen=max(self.burst_n * 4, 64))

    # ---------------- timeline lifecycle ----------------

    def start(self, kind: str, **meta) -> int:
        """Allocate a timeline; returns its id (0 when disabled — every
        downstream record() on id 0 is a no-op)."""
        if not self.enabled:
            return 0
        tl = next(self._tl_ids)
        m = {"kind": kind, "t_mono": time.monotonic()}
        if meta:
            m.update(meta)
        with self._meta_lock:
            self.timelines_started += 1
            self._timelines[tl] = m
            while len(self._timelines) > self._meta_cap:
                self._timelines.popitem(last=False)
        return tl

    def annotate(self, tl: int, **meta) -> None:
        """Attach metadata to a live timeline (e.g. the trace root span
        id, once known)."""
        if not self.enabled or not tl:
            return
        with self._meta_lock:
            m = self._timelines.get(tl)
            if m is not None:
                m.update(meta)

    # ---------------- the hot path ----------------

    def record(self, tl: int, kind: str, **fields) -> None:
        """Append one event. Near-free: one counter bump + one slot
        store. Callers on hot paths must guard `if RECORDER.enabled:`
        before building `fields` (oslint OSL505)."""
        if not self.enabled or not tl:
            return
        i = next(self._seq)
        self._slots[i % self.capacity] = (
            i, tl, time.monotonic(), kind, fields or None)

    def graft(self, tl: int, events: Optional[Sequence[dict]],
              node: str) -> None:
        """Stitch a remote node's serialized timeline events (carried on
        a distnode RPC response) into local timeline `tl` — the event
        analog of `Tracer.attach_remote`. Remote monotonic stamps are
        meaningless here, so they ride as `remote_t_mono` and the event
        takes a local receive-time stamp (ordering within the remote leg
        is preserved by `remote_seq`)."""
        if not self.enabled or not tl or not events:
            return
        for ev in events:
            if not isinstance(ev, dict):
                continue
            fields = {k: v for k, v in ev.items()
                      if k not in ("seq", "t_mono", "kind")}
            fields["node"] = node
            fields["remote_seq"] = ev.get("seq")
            fields["remote_t_mono"] = ev.get("t_mono")
            self.record(tl, str(ev.get("kind", "remote")), **fields)

    # ---------------- reads (cold paths) ----------------

    def _scan(self) -> List[tuple]:
        """Snapshot the ring's valid events in sequence order. Writers
        may race the scan; a slot read is atomic (one tuple ref), so the
        result is a consistent set of whole events."""
        out = [s for s in self._slots if s is not None]
        out.sort(key=lambda s: s[0])
        return out

    def timeline_events(self, tl: int,
                        events: Optional[List[tuple]] = None) -> List[dict]:
        """Serialized events for one timeline, oldest first (bounded by
        max_timeline_events, keeping the newest). Runs per distnode RPC
        leg, so without a pre-scanned `events` list it filters to the
        timeline BEFORE sorting — cost proportional to the timeline's
        own event count, not capacity·log(capacity)."""
        if events is not None:
            evs = [s for s in events if s[1] == tl]
        else:
            evs = [s for s in self._slots
                   if s is not None and s[1] == tl]
            evs.sort(key=lambda s: s[0])
        evs = evs[-self.max_timeline_events:]
        return [{"seq": s[0], "t_mono": round(s[2], 6), "kind": s[3],
                 **({k: _jsonable(v) for k, v in s[4].items()}
                    if s[4] else {})}
                for s in evs]

    def timeline_meta(self, tl: int) -> Optional[dict]:
        with self._meta_lock:
            m = self._timelines.get(tl)
            return dict(m) if m is not None else None

    def _wall(self, t_mono: float) -> float:
        return self._anchor_wall + (t_mono - self._anchor_mono)

    # ---------------- anomaly dumps ----------------

    def trigger(self, reason: str, tl_ids: Optional[Sequence[int]] = None,
                note: Optional[str] = None,
                force: bool = False) -> Optional[dict]:
        """Freeze the given timelines (None = the most recent ones in
        the ring) into a dump bundle. Storm-shaped reasons are
        rate-limited by `cooldown_s`; wedge/deadline-miss style reasons
        (and force=True) always dump."""
        if not self.enabled:
            return None
        now = time.monotonic()
        with self._dump_lock:
            self.trigger_counts[reason] = \
                self.trigger_counts.get(reason, 0) + 1
            if not force and reason in self._COOLDOWN_REASONS:
                last = self._last_trigger.get(reason)
                if last is not None and now - last < self.cooldown_s:
                    self.suppressed_triggers += 1
                    return None
            self._last_trigger[reason] = now
            bundle = self._build_bundle(reason, tl_ids, note, now)
            self._dumps.append(bundle)
        METRICS.counter("flight_recorder.dumps").inc()
        METRICS.counter(f"flight_recorder.dump.{reason}").inc()
        return bundle

    def _build_bundle(self, reason: str, tl_ids, note, now: float) -> dict:
        events = self._scan()
        if tl_ids:
            want = list(dict.fromkeys(int(t) for t in tl_ids if t))
        else:
            # manual snapshot: every timeline present in the ring,
            # newest first
            seen: "OrderedDict[int, None]" = OrderedDict()
            for s in reversed(events):
                seen.setdefault(s[1], None)
            want = list(seen)
        want = want[: self.max_dump_timelines]
        timelines = {}
        for tl in want:
            evs = self.timeline_events(tl, events)
            for ev in evs:
                ev["t_wall"] = round(self._wall(ev["t_mono"]), 6)
            timelines[str(tl)] = {"meta": _jsonable(self.timeline_meta(tl)),
                                  "events": evs}
        return {"id": next(self._dump_ids), "reason": reason,
                **({"note": note} if note else {}),
                "at_mono": round(now, 6),
                "at_wall": round(self._wall(now), 6),
                "timelines": timelines,
                "timeline_count": len(timelines)}

    def note_rejection(self, tl: int = 0) -> None:
        """Count one scheduler 429; when `burst_n` land inside
        `burst_window_s`, freeze the rejected timelines (a rejection
        storm is an anomaly even though each 429 alone is policy)."""
        if not self.enabled:
            return
        now = time.monotonic()
        with self._rej_lock:
            self._rejections.append((now, tl))
            recent = [(t, x) for (t, x) in self._rejections
                      if now - t <= self.burst_window_s]
        if len(recent) >= self.burst_n:
            self.trigger("rejection_burst",
                         [x for _, x in recent if x],
                         note=f"{len(recent)} scheduler rejections in "
                              f"{self.burst_window_s}s")

    def note_lock_inversion(self, first: str, second: str,
                            stack_now: str, stack_prior: str) -> None:
        """Freeze a dump when the runtime lock witness
        (devtools/lockwitness.py) observes an acquisition-order
        inversion — both stacks ride in the bundle so the two
        conflicting code paths are named even after the process moves
        on. Always dumps (force=True): a witnessed inversion is a
        latent deadlock, never storm noise."""
        if not self.enabled:
            return
        tl = self.start("lock_inversion", first=first, second=second)
        self.record(tl, "lock_inversion", first=first, second=second,
                    stack_now=stack_now, stack_prior=stack_prior)
        self.trigger("lock_inversion", [tl],
                     note=f"{second} acquired while holding {first} "
                          "after the opposite order was witnessed",
                     force=True)

    def dumps(self, limit: Optional[int] = None) -> List[dict]:
        with self._dump_lock:
            out = list(self._dumps)
        if limit is not None:
            out = out[-limit:]
        return list(reversed(out))

    # ---------------- stats + test hooks ----------------

    def stats(self) -> dict:
        events = self._scan()
        total = (events[-1][0] + 1) if events else 0
        with self._dump_lock:
            dump_meta = [{"id": d["id"], "reason": d["reason"],
                          "at_wall": d["at_wall"],
                          "timeline_count": d["timeline_count"]}
                         for d in reversed(self._dumps)]
            triggers = dict(self.trigger_counts)
            suppressed = self.suppressed_triggers
        return {"enabled": self.enabled,
                "capacity": self.capacity,
                "events": total,
                "retained_events": len(events),
                "overwritten_events": max(total - self.capacity, 0),
                "timelines_started": self.timelines_started,
                "dumps": dump_meta,
                "triggers": triggers,
                "suppressed_triggers": suppressed}

    def reset(self) -> None:
        """Drop every event, timeline and dump — isolation hook for
        tests and bench cells (mirrors MetricsRegistry.reset)."""
        self._slots = [None] * self.capacity
        self._seq = itertools.count()
        with self._meta_lock:
            self._timelines.clear()
            self.timelines_started = 0
        with self._dump_lock:
            self._dumps.clear()
            self._last_trigger.clear()
            self.trigger_counts.clear()
            self.suppressed_triggers = 0
        with self._rej_lock:
            self._rejections.clear()


# process-default recorder (one node per process, like TRACER/METRICS)
RECORDER = FlightRecorder()
