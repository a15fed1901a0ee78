"""Device failure detection: deterministic heartbeat over the device set.

Reference analog: `cluster/coordination/FollowersChecker.java` /
`LeaderChecker.java` — periodic pings with a consecutive-failure threshold
before a node is removed. Here the "followers" are accelerator chips: a
probe runs one tiny device computation AND FETCHES it (dispatch is
asynchronous: only a fetched result proves the chip answered). The caller
owns the clock: `tick()` is one heartbeat round
(a cron wrapper recovers the reference's scheduler), so tests and the
driver get reproducible failure sequences.

After `failure_threshold` CONSECUTIVE probe failures a device is declared
dead: every IndexService re-allocates its copies (promote surviving
replicas, rebuild moved ones — IndexService.fail_device), matching the
reference's allocation response to a left node."""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional


def default_prober(device) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    try:
        out = jax.device_put(jnp.ones((8,), jnp.float32), device)
        return bool(np.asarray(out + 1.0).sum() == 16.0)
    except Exception:
        return False


class FailureDetector:
    def __init__(self, node, failure_threshold: int = 3,
                 prober: Optional[Callable] = None,
                 probe_timeout_s: float = 10.0):
        self.node = node
        self.failure_threshold = failure_threshold
        self.prober = prober or default_prober
        self.probe_timeout_s = probe_timeout_s
        self.consecutive: Dict[int, int] = {}
        self.dead: set = set()
        self.rounds = 0
        self.last_tick: Optional[float] = None

    def _probe_with_timeout(self, dev) -> bool:
        """A wedged chip HANGS the fetch rather than raising — exactly the
        case the probe exists for — so the probe runs on a watchdog thread
        and a timeout counts as a failure. The orphaned thread parks on the
        dead fetch; it is daemonic and costs one thread per hung probe."""
        import threading
        result = {"ok": False}

        def run():
            try:
                result["ok"] = bool(self.prober(dev))
            except Exception:
                result["ok"] = False
        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(self.probe_timeout_s)
        if t.is_alive():
            return False
        return result["ok"]

    def _devices(self) -> List:
        import jax
        return list(jax.devices())

    def tick(self) -> List[dict]:
        """One heartbeat round over live devices. Returns the events."""
        self.rounds += 1
        self.last_tick = time.time()
        events: List[dict] = []
        for ordinal, dev in enumerate(self._devices()):
            if ordinal in self.dead:
                continue
            ok = self._probe_with_timeout(dev)
            if ok:
                if self.consecutive.get(ordinal):
                    events.append({"device": ordinal, "event": "recovered",
                                   "after_failures":
                                       self.consecutive[ordinal]})
                self.consecutive[ordinal] = 0
                continue
            self.consecutive[ordinal] = self.consecutive.get(ordinal, 0) + 1
            events.append({"device": ordinal, "event": "probe_failed",
                           "consecutive": self.consecutive[ordinal]})
            if self.consecutive[ordinal] >= self.failure_threshold:
                self.dead.add(ordinal)
                events.append({"device": ordinal, "event": "failed"})
                for svc in self.node.indices.values():
                    svc.fail_device(ordinal)
        return events

    def stats(self) -> dict:
        return {"rounds": self.rounds, "dead_devices": sorted(self.dead),
                "failure_threshold": self.failure_threshold,
                "suspect": {str(k): v for k, v in self.consecutive.items()
                            if v > 0}}


class MemberFailureDetector:
    """Cross-node sibling of `FailureDetector`: tracks consecutive RPC /
    probe failures per cluster MEMBER and feeds the finding back into
    shard-copy selection (cluster/routing.py `order_copies`) instead of
    letting a dead member be rediscovered at RPC time on every request.

    A member past `failure_threshold` consecutive failures is
    DEPRIORITIZED — demoted to the back of every shard's copy preference
    list — not removed: it still serves shards that have no other copy,
    and one successful probe or RPC restores it (reference
    FollowersChecker semantics: suspicion is cheap to enter, cheap to
    leave). The caller owns the clock: RPC outcomes arrive via
    `note_failure`/`note_success`, and `tick(members)` runs one explicit
    probe round over the suspects so recovery is deterministic in tests.
    """

    def __init__(self, failure_threshold: int = 3,
                 prober: Optional[Callable] = None,
                 probe_timeout_s: float = 1.0):
        self.failure_threshold = int(failure_threshold)
        self.prober = prober            # (member, addr) -> bool
        self.probe_timeout_s = float(probe_timeout_s)
        self._lock = threading.Lock()
        self.consecutive: Dict[str, int] = {}
        self._depri: set = set()
        # remediation-pinned members (serving/remediator.py): demoted in
        # copy preference like suspicion-deprioritized ones, but a
        # successful probe/RPC does NOT clear a pin — only the actuator's
        # own TTL/green release (unpin) does, so a flapping member can't
        # immediately re-promote itself mid-remediation
        self._pinned: set = set()
        self.rounds = 0

    def note_failure(self, member: str) -> bool:
        """Record one failed RPC/probe. Returns True when this crossing
        newly deprioritized the member."""
        with self._lock:
            n = self.consecutive.get(member, 0) + 1
            self.consecutive[member] = n
            if n >= self.failure_threshold and member not in self._depri:
                self._depri.add(member)
                return True
        return False

    def note_success(self, member: str) -> None:
        with self._lock:
            self.consecutive[member] = 0
            self._depri.discard(member)

    def deprioritized(self) -> set:
        with self._lock:
            return set(self._depri) | set(self._pinned)

    def pin(self, member: str) -> bool:
        """Remediation engage: demote `member` in every shard's copy
        preference until `unpin` (the paired release — oslint OSL603).
        Returns True when this call newly pinned it."""
        with self._lock:
            if member in self._pinned:
                return False
            self._pinned.add(member)
            return True

    def unpin(self, member: str) -> None:
        with self._lock:
            self._pinned.discard(member)

    def pinned(self) -> set:
        with self._lock:
            return set(self._pinned)

    def _default_probe(self, member: str, addr: str) -> bool:
        import json
        import os
        import urllib.request
        headers = {}
        # same node-to-node trust as the RPC wire (`distnode._http`):
        # without the cluster token a security-enabled member answers
        # 403 and a demoted peer could never probe-recover
        tok = os.environ.get("OPENSEARCH_TPU_CLUSTER_TOKEN")
        if tok:
            headers["X-Cluster-Token"] = tok
        try:
            req = urllib.request.Request(f"http://{addr}/_internal/ping",
                                         method="GET", headers=headers)
            with urllib.request.urlopen(
                    req, timeout=self.probe_timeout_s) as r:
                return bool(json.loads(r.read().decode()).get("ok"))
        except Exception:
            return False

    def tick(self, members: Dict[str, str]) -> List[dict]:
        """One probe round over the currently-suspect members. A
        successful probe clears the suspicion (and the deprioritization);
        a failed one deepens it. Returns the events."""
        self.rounds += 1
        probe = self.prober or self._default_probe
        events: List[dict] = []
        with self._lock:
            suspects = set(self._depri) | {
                m for m, n in self.consecutive.items() if n > 0}
        for member in sorted(suspects):
            addr = members.get(member)
            if addr is None:
                continue
            if probe(member, addr):
                after = self.consecutive.get(member, 0)
                self.note_success(member)
                events.append({"member": member, "event": "recovered",
                               "after_failures": after})
            else:
                crossed = self.note_failure(member)
                events.append({"member": member, "event": "probe_failed",
                               "consecutive": self.consecutive[member],
                               **({"deprioritized": True}
                                  if crossed else {})})
        return events

    def stats(self) -> dict:
        with self._lock:
            return {"failure_threshold": self.failure_threshold,
                    "rounds": self.rounds,
                    "deprioritized": sorted(self._depri),
                    "pinned": sorted(self._pinned),
                    "suspect": {m: n for m, n in self.consecutive.items()
                                if n > 0}}
