"""Closed-loop remediation actuator: from SLO burn to bounded action.

Every prior observability layer REPORTS: the burn-rate engine
(obs/slo.py) says a lane's objective is burning, query insights
(obs/insights.py) says WHICH query shapes are responsible, the member
failure detector (cluster/failure.py) says which peer is sick. This
module is the first subsystem that ACTS on those findings — the
load-shed actuator ROADMAP item 1 has promised since round 5. It
subscribes to firing ``slo.burn`` alerts and takes bounded, reversible
actions at the admission boundary:

- **shed_shape** — the alert's ``top_fingerprints`` become a shed set.
  At admission (rest/client.py, cluster/distnode.py) the request body is
  re-fingerprinted with `insights.fingerprint(body, lane)`; a matching
  BATCH-lane request is rejected with 429 + a ``Retry-After`` header
  (the shed), a matching INTERACTIVE request is demoted to the batch
  lane (the deprioritization — SCHEDULING priority only: callers keep
  recording SLIs/insights under the origin lane, or the demotion would
  hide the burn from the SLO that fired it) — offending shapes lose
  priority, they are never silently dropped mid-flight, and unlisted
  shapes are never touched. Fingerprint derivation is deterministic,
  so the decision for a given body is byte-stable across threads and
  nodes.
- **tighten_admission** — while engaged, the serving scheduler's
  admission cap contracts (`queue_cap * admission_factor`, 429s fire
  earlier with honest Retry-After hints derived from queue depth) and
  every wlm token-bucket admission spends ``wlm_cost`` tokens instead
  of one (utils/wlm.py) — the front door narrows without any
  configuration mutation to undo later.
- **deprioritize_member** — for transport-shaped alerts, the worst
  suspect in the `MemberFailureDetector` is PINNED to the back of every
  shard's copy preference (`member_fd.pin`); unlike ordinary suspicion,
  a lucky probe does not un-demote it — only this actuator's release
  path (`member_fd.unpin`) does.

Every action is **bounded and self-releasing** (oslint OSL603 enforces
the pairing statically): a hard TTL (`ttl_s`) releases it even if the
evaluation loop dies, and the green path releases it once the alerting
SLO has read ``ok`` continuously for `green_hold_s`. Hysteresis: the
multi-window burn rate already gates engagement on sustained pressure,
re-alerts within `engage_cooldown_s` refresh the existing actions'
TTLs instead of stacking new ones, and at most `max_actions` are ever
live. While a load-shaped SLO KEEPS firing with remediation engaged,
the tick loop periodically **re-attributes** — alerts are
edge-triggered and attribution is completion-time accounting, so a
flooding shape whose requests were still in flight at the first edge
only shows up in the window later; the actuator keeps pulling the
live top-K (paced by the same cooldown, same bounds) until the burn
clears. Every transition lands a flight-recorder event
(``remediation.engage`` / ``remediation.release``), an engage freezes a
``remediation`` dump bundle, and `GET /_remediation` serves the live
action table — federated across the fleet on the `/_internal` plane
like the observatory surfaces.

Disarmed (the default) the actuator is inert: the admission hot path is
one attribute read (`self._active`), and fingerprints are only derived
while a shed set is live. Tests and the traffic harness inject private
instances (`node.remediation`, `DistClusterNode.remediation_engine`) —
the obs_registry pattern.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

from ..utils.metrics import METRICS, MetricsRegistry
from ..utils.wlm import PressureRejectedException

__all__ = ["RemediationConfig", "Action", "Remediator", "REMEDIATOR"]

KINDS = ("shed_shape", "tighten_admission", "deprioritize_member")

# alert kinds whose blame is load-shaped (shed/tighten applies) vs
# transport-shaped (member deprioritization applies). rejection_rate is
# deliberately in NEITHER set: tightening admission on a rejection burn
# would manufacture more rejections and self-sustain the alert.
_LOAD_KINDS = ("latency", "error_rate", "availability")
_TRANSPORT_KINDS = ("counter_ratio", "availability")

# Retry-After hints are clamped: an honest "come back later" must never
# tell a client to go away for a whole TTL epoch
_RETRY_AFTER_CAP_S = 30.0


class RemediationConfig:
    """Bounds and clocks for every action the actuator may take (the
    action table in docs/RESILIENCE.md "Self-healing loop")."""

    def __init__(self, ttl_s: float = 60.0,
                 green_hold_s: float = 2.0,
                 engage_cooldown_s: float = 1.0,
                 max_actions: int = 8,
                 max_shed_shapes: int = 3,
                 admission_factor: float = 0.5,
                 wlm_cost: float = 2.0,
                 retry_after_s: float = 1.0):
        # hard auto-release bound: an engaged action with a dead
        # evaluation loop still expires (checked lazily at admission too)
        self.ttl_s = float(ttl_s)
        # release hysteresis: the alerting SLO must read ok continuously
        # this long before the action lifts (a single green tick between
        # two burn windows must not flap the actuator)
        self.green_hold_s = float(green_hold_s)
        # engage hysteresis: re-alerts inside the cooldown refresh TTLs
        # instead of stacking new actions
        self.engage_cooldown_s = float(engage_cooldown_s)
        self.max_actions = int(max_actions)
        self.max_shed_shapes = int(max_shed_shapes)
        # scheduler queue-cap contraction while tighten_admission holds
        self.admission_factor = float(admission_factor)
        # wlm token cost per admission while tighten_admission holds
        self.wlm_cost = float(wlm_cost)
        self.retry_after_s = float(retry_after_s)
        if not 0.0 < self.admission_factor <= 1.0:
            raise ValueError("admission_factor must be in (0, 1]")
        if self.ttl_s <= 0:
            raise ValueError("remediation ttl_s must be positive")

    def describe(self) -> dict:
        return {"ttl_s": self.ttl_s, "green_hold_s": self.green_hold_s,
                "engage_cooldown_s": self.engage_cooldown_s,
                "max_actions": self.max_actions,
                "max_shed_shapes": self.max_shed_shapes,
                "admission_factor": self.admission_factor,
                "wlm_cost": self.wlm_cost}


class Action:
    """One live remediation action: what was engaged, why, and when it
    must be gone again."""

    __slots__ = ("kind", "target", "slo", "engaged_mono", "ttl_s",
                 "green_since_mono", "meta")

    def __init__(self, kind: str, target: str, slo: str, now: float,
                 ttl_s: float, meta: Optional[dict] = None):
        self.kind = kind
        self.target = target
        self.slo = slo
        self.engaged_mono = now
        self.ttl_s = float(ttl_s)
        self.green_since_mono: Optional[float] = None
        self.meta = dict(meta or {})

    @property
    def key(self) -> tuple:
        return (self.kind, self.target)

    def expired(self, now: float) -> bool:
        return now - self.engaged_mono >= self.ttl_s

    def describe(self, now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        return {"kind": self.kind, "target": self.target,
                "slo": self.slo,
                "age_s": round(now - self.engaged_mono, 3),
                "ttl_s": self.ttl_s,
                "ttl_remaining_s": round(
                    max(self.ttl_s - (now - self.engaged_mono), 0.0), 3),
                **({"meta": self.meta} if self.meta else {})}


class Remediator:
    """The closed control loop. `arm()` subscribes it to an SLO engine's
    firing alerts and a sampler's tick (the release clock); `admit()` is
    the only call on the serving hot path."""

    def __init__(self, config: Optional[RemediationConfig] = None,
                 registry: Optional[MetricsRegistry] = None,
                 recorder=None):
        self.config = config or RemediationConfig()
        self.registry = registry if registry is not None else METRICS
        self._recorder = recorder      # None -> module RECORDER, lazily
        self._lock = threading.Lock()
        self._actions: "OrderedDict[tuple, Action]" = OrderedDict()
        self._history: deque = deque(maxlen=64)
        # wiring (set by arm)
        self.armed = False
        self.engine = None             # obs.slo.SLOEngine
        self.sampler = None
        self.member_fd = None          # cluster.failure.MemberFailureDetector
        self.insights_engine = None    # None -> module INSIGHTS, lazily
        self._last_engage_mono: Dict[str, float] = {}   # per-SLO cooldown
        # load-shaped SLOs with live remediation: while one KEEPS
        # firing, tick() re-pulls attribution and widens the shed set
        # (bounded by max_shed_shapes per pull / max_actions total) —
        # alerts are edge-triggered, but a flooding shape whose
        # requests were still in flight at the first edge only becomes
        # visible to completion-time accounting later
        self._burning_ctx: Dict[str, dict] = {}
        # counters (mutated under the lock, mirrored into the registry)
        self.engaged_total = 0
        self.released_total = 0
        self.shed_total = 0
        self.deprioritized_total = 0
        # ---- admission fast-path snapshots (GIL-atomic attribute swaps;
        # the hot path reads these WITHOUT the lock — the sanctioned
        # lock-free pattern, see docs/STATIC_ANALYSIS.md "Concurrency
        # suite": single-reference rebind-then-swap only; any
        # read-modify-write here must move under self._lock) ----
        self._active = False
        self._shed: frozenset = frozenset()
        self._tightened = False
        # earliest TTL deadline among live actions: admit() consults it
        # so the hard bound holds even with a dead evaluation loop
        self._next_expiry = float("inf")

    # ---------------- arm / disarm ----------------

    def arm(self, node=None, slo_engine=None, sampler=None,
            member_fd=None, insights=None) -> None:
        """Wire the loop: alerts in from the SLO engine, the release
        clock from the sampler tick. Idempotent."""
        if insights is not None:
            self.insights_engine = insights
        if slo_engine is None and node is not None:
            slo_engine = getattr(node, "slo", None)
        if slo_engine is None:
            from ..obs.slo import SLO_ENGINE
            slo_engine = SLO_ENGINE
        new_sampler = sampler if sampler is not None \
            else slo_engine.sampler
        # re-arming against a DIFFERENT engine/sampler must drop the
        # old subscriptions first, or the abandoned engine's alerts
        # would keep driving this actuator (idempotence means one live
        # wiring, not an accumulating set)
        if self.engine is not None and self.engine is not slo_engine:
            self.engine.remove_alert_listener(self.on_alert)
        if self.sampler is not None and self.sampler is not new_sampler:
            self.sampler.remove_listener(self._on_tick)
        self.engine = slo_engine
        self.sampler = new_sampler
        if member_fd is not None:
            self.member_fd = member_fd
        self.engine.add_alert_listener(self.on_alert)
        self.sampler.add_listener(self._on_tick)
        self.armed = True

    def disarm(self) -> None:
        """Release every live action and unsubscribe. The actuator must
        never leave state behind: disarm returns the node to exactly the
        unremediated configuration."""
        # flip armed FIRST: an in-flight tick()'s re-attribution pass
        # (which snapshots _burning_ctx before we clear it) checks the
        # flag per engagement and must not re-engage after the release
        self.armed = False
        if self.engine is not None:
            self.engine.remove_alert_listener(self.on_alert)
        if self.sampler is not None:
            self.sampler.remove_listener(self._on_tick)
        released = []
        with self._lock:
            for action in list(self._actions.values()):
                released.append(
                    self._release_locked(action, why="disarm"))
            self._burning_ctx.clear()
            self._rebuild_locked()
        for row in released:
            self._record_release(row)
        self.armed = False

    # ---------------- the engage side (alert listener) ----------------

    def on_alert(self, alert: dict) -> None:
        """One firing `slo.burn` alert -> the engage policy:

        - load-shaped kinds (latency / error_rate / availability): shed
          the alert's top fingerprints + tighten admission;
        - transport-shaped kinds (counter_ratio / availability): pin the
          failure detector's worst suspect member;
        - rejection_rate: no amplification — rejections are already the
          actuator's own exhaust, acting on them would self-sustain.

        Re-alerts inside `engage_cooldown_s` refresh live TTLs only."""
        if not isinstance(alert, dict):
            return
        slo = str(alert.get("slo", ""))
        kind = str(alert.get("slo_kind", ""))
        now = time.monotonic()
        with self._lock:
            last = self._last_engage_mono.get(slo)
            refresh_only = (last is not None
                            and now - last < self.config.engage_cooldown_s)
            self._last_engage_mono[slo] = now
            if refresh_only:
                for a in self._actions.values():
                    if a.slo == slo:
                        a.engaged_mono = now
                        a.green_since_mono = None
                # the lazy-expiry snapshot must follow the refreshed
                # TTLs, or admit() would run a full tick per request
                # once the ORIGINAL deadline passes
                self._rebuild_locked()
                return
        if kind in _LOAD_KINDS:
            fps = [e.get("fingerprint")
                   for e in (alert.get("top_fingerprints") or [])
                   if isinstance(e, dict) and e.get("fingerprint")]
            for key in fps[: self.config.max_shed_shapes]:
                self._engage("shed_shape", str(key), slo,
                             meta={"lane": alert.get("lane")})
            self._engage("tighten_admission", "", slo)
        if kind in _TRANSPORT_KINDS and self.member_fd is not None:
            member = self._worst_suspect()
            if member is not None:
                self._engage("deprioritize_member", member, slo)
        if kind in _LOAD_KINDS or kind in _TRANSPORT_KINDS:
            with self._lock:
                self._burning_ctx[slo] = {"kind": kind,
                                          "lane": alert.get("lane")}

    def _worst_suspect(self) -> Optional[str]:
        """The member the failure detector blames most (max consecutive
        failures, name-ordered tie break); None when nobody is suspect —
        a transport burn with no named culprit engages nothing."""
        try:
            st = self.member_fd.stats()
        except Exception:       # noqa: BLE001 — blame input is advisory
            return None
        suspect = dict(st.get("suspect") or {})
        for m in st.get("deprioritized") or []:
            suspect.setdefault(m, 1 << 30)
        if not suspect:
            return None
        return sorted(suspect.items(), key=lambda kv: (-kv[1], kv[0]))[0][0]

    def _engage(self, kind: str, target: str, slo: str,
                meta: Optional[dict] = None,
                guard_armed: bool = False) -> None:
        now = time.monotonic()
        with self._lock:
            if guard_armed and not self.armed:
                # listener-driven engage racing a disarm: the armed
                # re-check must be ATOMIC with the insert, or a tick in
                # flight could strand an action (and a member pin) with
                # every release listener already gone
                return
            existing = self._actions.get((kind, target))
            if existing is not None:
                # refresh: hysteresis extends the bound, never stacks
                # (and the lazy-expiry snapshot follows the new TTL)
                existing.engaged_mono = now
                existing.green_since_mono = None
                self._rebuild_locked()
                return
            if len(self._actions) >= self.config.max_actions:
                self.registry.counter("remediation.bounded_out").inc()
                return
            action = Action(kind, target, slo, now, self.config.ttl_s,
                            meta)
            self._actions[action.key] = action
            self.engaged_total += 1
            self._history.append({"event": "engage", "kind": kind,
                                  "target": target, "slo": slo,
                                  "at_mono": round(now, 6)})
            self._rebuild_locked()
        if kind == "deprioritize_member" and self.member_fd is not None:
            self.member_fd.pin(target)
        self.registry.counter("remediation.engaged_total").inc()
        rec = self._rec()
        if rec is not None and rec.enabled:
            tl = rec.start("remediation", action=kind, slo=slo)
            if tl:
                rec.record(tl, "remediation.engage", action=kind,
                           target=target, slo=slo,
                           ttl_s=self.config.ttl_s)
                rec.trigger("remediation", [tl],
                            note=f"remediation [{kind}] target "
                                 f"[{target or '-'}] for SLO [{slo}]")

    # ---------------- the release side (sampler tick) ----------------

    def _on_tick(self, _sampler) -> None:
        self.tick()

    def tick(self, now: Optional[float] = None) -> List[dict]:
        """One release pass: TTL expiry (hard bound) and green release
        (the alerting SLO read ok for `green_hold_s`). Returns the
        release records, for tests and the harness gate."""
        now = time.monotonic() if now is None else now
        released: List[dict] = []
        with self._lock:
            for action in list(self._actions.values()):
                if action.expired(now):
                    released.append(
                        self._release_locked(action, why="ttl", now=now))
                    continue
                if self._slo_green(action.slo):
                    if action.green_since_mono is None:
                        action.green_since_mono = now
                    elif (now - action.green_since_mono
                          >= self.config.green_hold_s):
                        released.append(self._release_locked(
                            action, why="green", now=now))
                else:
                    action.green_since_mono = None
            if released:
                self._rebuild_locked()
        for rec_row in released:
            self._record_release(rec_row)
        self._reattribute(now)
        return released

    def _reattribute(self, now: float) -> None:
        """While an SLO KEEPS firing with remediation engaged,
        periodically re-pull attribution and keep the actions live.
        Alerts are edge-triggered: the first edge's top-K can miss the
        true offender when its requests were still in flight
        (completion-time accounting), and a burn outlasting `ttl_s`
        would otherwise silently lapse its tighten/pin actions with no
        new edge to re-engage them. Paced by `engage_cooldown_s`,
        bounded like any engagement."""
        with self._lock:
            ctxs = dict(self._burning_ctx)
        for slo, ctx in ctxs.items():
            if not self.armed:
                # disarm raced this pass: re-engaging now would strand
                # actions with every release listener already removed
                return
            if self._slo_green(slo):
                with self._lock:
                    self._burning_ctx.pop(slo, None)
                continue
            with self._lock:
                last = self._last_engage_mono.get(slo, -1e18)
                if now - last < self.config.engage_cooldown_s:
                    continue
                self._last_engage_mono[slo] = now
            kind = ctx.get("kind")
            if kind in _TRANSPORT_KINDS and self.member_fd is not None:
                member = self._worst_suspect()
                if member is not None:
                    self._engage("deprioritize_member", member, slo,
                                 meta={"via": "reattribution"},
                                 guard_armed=True)
            if kind not in _LOAD_KINDS:
                continue
            # still-burning load alert: keep the admission tightened
            # (refresh, or re-engage if it TTL'd out mid-burn) and
            # widen the shed set from the live window
            self._engage("tighten_admission", "", slo,
                         guard_armed=True)
            window_s = self._slo_window(slo)
            try:
                fps = self._insights().top_fingerprints(
                    window_s, n=self.config.max_shed_shapes)
            except Exception:   # noqa: BLE001 — attribution is advisory
                continue
            for e in fps:
                key = (e or {}).get("fingerprint")
                if key:
                    self._engage("shed_shape", str(key), slo,
                                 meta={"lane": ctx.get("lane"),
                                       "via": "reattribution"},
                                 guard_armed=True)

    def _slo_window(self, slo_name: str) -> float:
        eng = self.engine
        try:
            s = eng._slos.get(slo_name) if eng is not None else None
        except Exception:       # noqa: BLE001
            s = None
        return float(getattr(s, "slow_window_s", 60.0))

    def _insights(self):
        if self.insights_engine is not None:
            return self.insights_engine
        from ..obs.insights import INSIGHTS
        return INSIGHTS

    def _slo_green(self, slo_name: str) -> bool:
        """ok iff the engine knows the objective and it is not firing;
        a disarmed/unknown objective reads green (nothing left to hold
        the action open — the TTL still bounds it)."""
        eng = self.engine
        if eng is None:
            return True
        try:
            st = eng._status.get(slo_name)       # engine-lock-free read
        except Exception:       # noqa: BLE001 — release must never wedge
            return True
        return st is None or st.get("state") != "firing"

    def _release_locked(self, action: Action, why: str,
                        now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        self._actions.pop(action.key, None)
        self.released_total += 1
        row = {"event": "release", "kind": action.kind,
               "target": action.target, "slo": action.slo, "why": why,
               "held_s": round(now - action.engaged_mono, 3),
               "at_mono": round(now, 6)}
        self._history.append(row)
        return row

    def _record_release(self, row: dict) -> None:
        if row["kind"] == "deprioritize_member" \
                and self.member_fd is not None:
            # liveness check AND unpin atomically under the actuator
            # lock: a concurrent re-engage inserts its action under the
            # same lock before pinning, so either we see it live (skip
            # the unpin) or our unpin completes before its pin lands —
            # a stale release can never strip a live action's pin.
            # (lock order self._lock -> fd._lock; the detector never
            # calls back into the actuator, so no inversion exists)
            with self._lock:
                if ("deprioritize_member",
                        row["target"]) not in self._actions:
                    self.member_fd.unpin(row["target"])
        self.registry.counter("remediation.released_total").inc()
        rec = self._rec()
        if rec is not None and rec.enabled:
            tl = rec.start("remediation", action=row["kind"],
                           slo=row["slo"])
            if tl:
                rec.record(tl, "remediation.release",
                           action=row["kind"], target=row["target"],
                           why=row["why"], held_s=row["held_s"])

    def _rebuild_locked(self) -> None:
        """Recompute the lock-free admission snapshots. Called under the
        lock; the swaps themselves are single attribute writes."""
        shed = frozenset(a.target for a in self._actions.values()
                         if a.kind == "shed_shape")
        tightened = any(a.kind == "tighten_admission"
                        for a in self._actions.values())
        self._shed = shed
        self._tightened = tightened
        self._active = bool(self._actions)
        self._next_expiry = min(
            (a.engaged_mono + a.ttl_s for a in self._actions.values()),
            default=float("inf"))
        self.registry.gauge("remediation.active_actions").set(
            float(len(self._actions)))

    # ---------------- the admission surface (hot path) ----------------

    @property
    def active(self) -> bool:
        return self._active

    @property
    def tightened(self) -> bool:
        return self._tightened

    def queue_factor(self) -> float:
        """Scheduler admission contraction: 1.0 unremediated."""
        return self.config.admission_factor if self._tightened else 1.0

    def wlm_cost(self) -> float:
        """wlm token cost per admission: 1.0 unremediated."""
        return self.config.wlm_cost if self._tightened else 1.0

    def admit(self, body, lane: str) -> str:
        """The admission-time fingerprint match. Returns the (possibly
        demoted) lane; raises PressureRejectedException (429 +
        Retry-After) for a shed batch-lane shape. Deterministic per
        body+lane — identical bodies always get identical decisions —
        and O(1) when no shed set is live."""
        if not self._active:
            return lane
        # the TTL is a HARD bound even with a dead evaluation loop:
        # admission itself retires expired actions lazily (the
        # RemediationConfig contract) — one monotonic read on the
        # already-remediated path, nothing on the inactive one
        if time.monotonic() >= self._next_expiry:
            self.tick()
            if not self._active:
                return lane
        shed = self._shed
        if not shed:
            return lane
        from ..obs.insights import fingerprint
        key = fingerprint(body if isinstance(body, dict) else {},
                          lane)[0]
        if key not in shed:
            return lane
        if lane == "batch":
            with self._lock:
                self.shed_total += 1
                retry = self._retry_after_locked(key)
            self.registry.counter("remediation.shed_total").inc()
            # the consistent rejection naming (docs/SERVING.md): every
            # admission-layer 429 — wlm, scheduler, remediation —
            # mirrors into serving.lane.{lane}.rejected
            self.registry.counter(
                f"serving.lane.{lane}.rejected").inc()
            raise PressureRejectedException(
                f"shape [{key}] is being shed by remediation "
                f"(SLO burn); retry after {retry:.0f}s",
                retry_after_s=retry, source="remediation")
        # interactive traffic is never hard-rejected by shape: it is
        # DEPRIORITIZED — demoted to the batch lane, where it only takes
        # the scheduler's leftover flush slots
        with self._lock:
            self.deprioritized_total += 1
        self.registry.counter("remediation.deprioritized_total").inc()
        return "batch"

    def _retry_after_locked(self, key: str) -> float:
        a = self._actions.get(("shed_shape", key))
        if a is None:
            return self.config.retry_after_s
        remaining = a.ttl_s - (time.monotonic() - a.engaged_mono)
        return min(max(remaining, self.config.retry_after_s, 1.0),
                   _RETRY_AFTER_CAP_S)

    # ---------------- surfaces ----------------

    def status(self) -> dict:
        """`GET /_remediation` payload: live action table, recent
        engage/release history, bounds, counters."""
        now = time.monotonic()
        with self._lock:
            active = [a.describe(now) for a in self._actions.values()]
            history = list(self._history)
            counters = {"engaged_total": self.engaged_total,
                        "released_total": self.released_total,
                        "shed_total": self.shed_total,
                        "deprioritized_total": self.deprioritized_total}
        return {"armed": self.armed, "active": active,
                "tightened": self._tightened,
                "shed_fingerprints": sorted(self._shed),
                "history": history, "counters": counters,
                "config": self.config.describe()}

    def stats(self) -> dict:
        """`_nodes/stats` "remediation" block (compact: no history)."""
        with self._lock:
            return {"armed": self.armed,
                    "active_actions": len(self._actions),
                    "tightened": self._tightened,
                    "engaged_total": self.engaged_total,
                    "released_total": self.released_total,
                    "shed_total": self.shed_total,
                    "deprioritized_total": self.deprioritized_total}

    def reset(self) -> None:
        """Test/bench isolation hook (the METRICS.reset pattern):
        disarm + drop history and counters."""
        self.disarm()
        with self._lock:
            self._history.clear()
            self._last_engage_mono.clear()
            self.engaged_total = self.released_total = 0
            self.shed_total = self.deprioritized_total = 0

    def _rec(self):
        if self._recorder is not None:
            return self._recorder
        from ..obs.flight_recorder import RECORDER
        return RECORDER


# process-default actuator (one node per process, like METRICS/RECORDER);
# disarmed until the traffic harness or an operator arms it
REMEDIATOR = Remediator()
