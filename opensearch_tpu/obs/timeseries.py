"""Time-series retention: a bounded ring of periodic registry snapshots.

PR 3's registry answers "what are the totals NOW"; this module answers
"what happened over the last N seconds" — the missing dimension for
debugging a chaos or traffic run: qps, queue depth, batch size, block
skip rate, HBM residency, and retry/failover counters become queryable
*series* (`GET /_nodes/stats/history?metric=...&window=...`) instead of
two hand-polled endpoint reads diffed in a notebook. Reference analog:
the OpenSearch Performance Analyzer's on-node metric store (fixed
retention, pull-based), scaled to this engine's one-process reality.

Sampler discipline (oslint OSL509 encodes all three statically):

- **Monotonic clock only.** Sample timestamps come from
  `time.monotonic()`; an NTP step must never reorder a series or produce
  a negative rate. Wall-clock display conversion goes through one
  (wall, mono) anchor captured at construction, the flight-recorder
  pattern.
- **Bounded ring.** Samples land in a `deque(maxlen=capacity)` — a
  sampler that `list.append`s forever is a slow memory leak wearing an
  observability costume.
- **Fixed per-tick cost.** A tick snapshots counter/gauge values (plain
  dict copies) and histogram (count, sum) pairs for every instrument,
  but full BIN maps only for explicitly tracked histograms (the SLO
  engine registers the ones its objectives window over) — the tick cost
  must not grow with how many latency sketches the process ever touched.

Threading: one daemon thread per sampler, parked on an `Event.wait`
(stoppable, not sleep-polling). The process singleton `SAMPLER` mirrors
METRICS/RECORDER/LEDGER — one node per process is the deployment
reality; co-resident test nodes share the ring exactly like they share
`/_metrics`. The thread does NOT auto-start: tests drive `sample_once()`
deterministically, servers and benches call `ensure_started()`.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

from ..utils.metrics import METRICS, MetricsRegistry, sketch_percentile

__all__ = ["TimeSeriesSampler", "SAMPLER"]


class _Sample:
    """One tick: monotonic stamp + counter/gauge values + histogram
    (count, sum) pairs + full bins for tracked histograms."""

    __slots__ = ("t_mono", "counters", "gauges", "hists", "bins")

    def __init__(self, t_mono: float, counters: Dict[str, float],
                 gauges: Dict[str, float],
                 hists: Dict[str, tuple],
                 bins: Dict[str, Dict[int, int]]):
        self.t_mono = t_mono
        self.counters = counters
        self.gauges = gauges
        self.hists = hists
        self.bins = bins


class TimeSeriesSampler:
    """Bounded-ring periodic snapshots of a MetricsRegistry with
    delta/rate derivation on read."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 interval_s: float = 1.0, capacity: int = 512):
        self.registry = registry if registry is not None else METRICS
        self.interval_s = float(interval_s)
        if self.interval_s <= 0:
            raise ValueError("sampler interval must be > 0")
        self.capacity = int(capacity)
        if self.capacity < 2:
            raise ValueError("sampler capacity must be >= 2 (rates need "
                             "two points)")
        # the ring: bounded by construction (oslint OSL509)
        self._ring: deque = deque(maxlen=self.capacity)
        self._ring_lock = threading.Lock()
        self._track: set = set()          # histogram names sampled w/ bins
        self._listeners: List[Callable[["TimeSeriesSampler"], None]] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._state_lock = threading.Lock()
        self.ticks = 0
        # wall display anchor (single pair; samples carry monotonic only)
        self._anchor_wall = time.time()
        self._anchor_mono = time.monotonic()

    # ---------------- configuration ----------------

    def track_histogram(self, *names: str) -> None:
        """Sample full bin maps for these histograms, enabling windowed
        percentiles (`window_percentile`). The SLO engine registers the
        histograms its latency objectives read."""
        self._track.update(names)

    def add_listener(self, fn: Callable[["TimeSeriesSampler"], None]
                     ) -> None:
        """Called after every tick with the sampler (the SLO engine's
        evaluation hook). Listeners run on the sampler thread; they must
        be quick and must not raise."""
        if fn not in self._listeners:
            self._listeners.append(fn)  # oslint: disable=OSL509 -- listener registry: one append per arm()/registration, never per tick

    def remove_listener(self, fn) -> None:
        if fn in self._listeners:
            self._listeners.remove(fn)

    # ---------------- lifecycle ----------------

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def ensure_started(self) -> None:
        with self._state_lock:
            if self.running:
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="ostpu-ts-sampler", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._state_lock:
            t = self._thread
            self._thread = None
        self._stop.set()
        if t is not None and t.is_alive():
            t.join(timeout=2.0)

    def reset(self) -> None:
        """Drop the ring — isolation hook for tests/bench cells
        (mirrors MetricsRegistry.reset). Tracking and listeners stay."""
        with self._ring_lock:
            self._ring.clear()
            self.ticks = 0

    def _run(self) -> None:
        # Event.wait is the stoppable park (not sleep-polling: the stop()
        # signal wakes it immediately); monotonic cadence
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:       # noqa: BLE001 — a sampler must never
                pass                # take the process down with it

    # ---------------- the tick ----------------

    def sample_once(self) -> None:
        """One snapshot into the ring + listener fan-out. Public so tests
        and the deadline-free single-node path can tick deterministically
        without the thread."""
        reg = self.registry
        with reg._lock:
            counters = {n: c.value for n, c in reg._counters.items()}
            gauges = {n: g.value for n, g in reg._gauges.items()}
            hitems = list(reg._hists.items())
        hists: Dict[str, tuple] = {}
        bins: Dict[str, Dict[int, int]] = {}
        for n, h in hitems:
            with h._lock:
                hists[n] = (h.count, h.sum_ms)
                if n in self._track:
                    bins[n] = dict(h._bins)
        s = _Sample(time.monotonic(), counters, gauges, hists, bins)
        with self._ring_lock:
            self._ring.append(s)
            self.ticks += 1
        for fn in list(self._listeners):
            try:
                fn(self)
            except Exception:       # noqa: BLE001 — a listener fault must
                # not kill the ring; counted, never silent (OSL508 spirit)
                reg.counter("timeseries.listener_errors").inc()

    # ---------------- reads ----------------

    def _window(self, window_s: float) -> List[_Sample]:
        with self._ring_lock:
            samples = list(self._ring)
        if not samples:
            return []
        cutoff = samples[-1].t_mono - float(window_s)
        # keep one sample BEFORE the cutoff when available: deltas over
        # the window need the entering value
        out = [s for s in samples if s.t_mono >= cutoff]
        older = [s for s in samples if s.t_mono < cutoff]
        if older:
            out = [older[-1]] + out
        return out

    @staticmethod
    def _metric_value(s: _Sample, metric: str):
        if metric in s.counters:
            return ("counter", s.counters[metric])
        if metric in s.gauges:
            return ("gauge", s.gauges[metric])
        if metric in s.hists:
            return ("histogram", s.hists[metric])
        return (None, None)

    def history(self, metric: str, window_s: float = 60.0) -> dict:
        """The `_nodes/stats/history` payload for one metric: raw points
        plus the derived per-interval rate for monotonic kinds (counters
        and histogram counts — qps is `search.lane.*.requests` under
        this derivation). Gauges report values only. Timestamps carry
        both the monotonic stamp (exact spacing) and an anchored wall
        stamp (display)."""
        samples = self._window(window_s)
        points = []
        prev = None
        kind_seen = None
        for s in samples:
            kind, v = self._metric_value(s, metric)
            if kind is None:
                prev = None
                continue
            kind_seen = kind
            if kind == "histogram":
                cnt, sm = v
                pt = {"t_mono": round(s.t_mono, 6),
                      "t_wall": round(self._wall(s.t_mono), 3),
                      "count": cnt, "sum_ms": round(sm, 3)}
                if prev is not None:
                    dt = s.t_mono - prev[0]
                    dc = cnt - prev[1][0]
                    if dt > 0:
                        pt["rate"] = round(dc / dt, 4)
                        dsum = sm - prev[1][1]
                        pt["mean_ms"] = (round(dsum / dc, 4) if dc > 0
                                         else None)
            else:
                pt = {"t_mono": round(s.t_mono, 6),
                      "t_wall": round(self._wall(s.t_mono), 3),
                      "value": v}
                if kind == "counter" and prev is not None:
                    dt = s.t_mono - prev[0]
                    if dt > 0:
                        pt["rate"] = round((v - prev[1]) / dt, 4)
            points.append(pt)
            prev = (s.t_mono, v)
        return {"metric": metric, "kind": kind_seen,
                "window_s": float(window_s),
                "interval_s": self.interval_s, "points": points}

    def counter_delta(self, metric: str, window_s: float) -> float:
        """Counter (or histogram-count) increase across the window —
        the SLO engine's bad/total event source. Instruments are
        create-on-first-use, so a metric ABSENT from a snapshot was
        definitionally 0 then — a counter born mid-window contributes
        its full value, not a silent 0 delta. Clamped at 0: a registry
        reset mid-window must not produce a negative burn."""
        samples = self._window(window_s)
        if len(samples) < 2:
            return 0.0
        vals = []
        for s in samples:
            kind, v = self._metric_value(s, metric)
            if kind == "histogram":
                vals.append(v[0])
            elif kind is not None:
                vals.append(v)
            else:
                vals.append(0.0)
        return max(float(vals[-1]) - float(vals[0]), 0.0)

    def window_hist_delta(self, name: str, window_s: float) -> dict:
        """The tracked histogram's bin delta across the window (wire
        shape) — windowed percentiles via `sketch_percentile`, and the
        above-threshold counting latency SLOs burn on. A histogram that
        did not EXIST at a tick reads as empty bins then (create-on-
        first-use); a tick where it existed but was untracked is
        unusable and skipped."""
        pts = []
        for s in self._window(window_s):
            if name in s.bins:
                pts.append((s.t_mono, s.bins[name]))
            elif name not in s.hists:
                pts.append((s.t_mono, {}))    # born later: zero baseline
        if len(pts) < 2:
            return {"bins": {}, "count": 0}
        first, last = pts[0][1], pts[-1][1]
        bins = {}
        for b, c in last.items():
            d = c - first.get(b, 0)
            if d > 0:
                bins[b] = d
        return {"bins": bins,
                "count": sum(bins.values()),
                "span_s": round(pts[-1][0] - pts[0][0], 6)}

    def window_percentile(self, name: str, window_s: float,
                          p: float) -> Optional[float]:
        d = self.window_hist_delta(name, window_s)
        return sketch_percentile(d["bins"], d["count"], p)

    def window_over_budget(self, name: str, window_s: float,
                           budget_ms: float) -> tuple:
        """(over, total) request counts for the window: how many recorded
        latencies exceeded the budget. Bin-granular: a budget inside a
        bin counts the whole bin as within-budget iff the bin's
        representative value is <= budget (deterministic, ~0.5% relative
        error at the boundary — the sketch's own resolution)."""
        from ..ops.aggs import ddsketch_value
        d = self.window_hist_delta(name, window_s)
        total = d["count"]
        over = sum(c for b, c in d["bins"].items()
                   if float(ddsketch_value(b)) > float(budget_ms))
        return over, total

    def _wall(self, t_mono: float) -> float:
        return self._anchor_wall + (t_mono - self._anchor_mono)

    def stats(self) -> dict:
        """`_nodes/stats` "timeseries" block."""
        with self._ring_lock:
            n = len(self._ring)
            newest = self._ring[-1].t_mono if n else None
            oldest = self._ring[0].t_mono if n else None
        return {"running": self.running,
                "interval_s": self.interval_s,
                "capacity": self.capacity,
                "samples": n,
                "ticks": self.ticks,
                "span_s": (round(newest - oldest, 3)
                           if n >= 2 else 0.0),
                "tracked_histograms": sorted(self._track)}


# process-default sampler (one node per process, like METRICS/RECORDER)
SAMPLER = TimeSeriesSampler()
