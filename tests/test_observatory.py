"""Fleet observatory (ISSUE 10): metrics federation, time-series
retention, and the SLO burn-rate engine.

- DDSketch `merge()` property tests: commutativity, associativity, and
  UNION PARITY — a sketch merged from two nodes answers every
  nearest-rank percentile identically to one sketch fed the union
  stream (the math `_cluster/stats` fleet percentiles stand on).
- Prometheus exposition: golden file, HELP/TYPE pairs, the `node`
  label, stable sanitization.
- Federation over a live 2-node cluster (`cluster/distnode.py`):
  merged-sketch fleet percentiles vs a single-node oracle, counter
  sums, per-node gauges, `_nodes/stats` + `hot_threads` + history
  fan-out, and honest per-node `failed` degradation when a member dies.
- Time-series retention (obs/timeseries.py): bounded ring, monotonic
  rates, windowed percentiles.
- SLO engine (obs/slo.py): burn-rate math, multi-window firing, the
  `slo.burn` flight-recorder dump carrying the offending window's
  series, resolution, and chaos detection on a cluster.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from opensearch_tpu.obs.flight_recorder import RECORDER
from opensearch_tpu.obs.slo import SLO, SLOEngine, default_slos
from opensearch_tpu.obs.timeseries import TimeSeriesSampler
from opensearch_tpu.rest.client import ApiError, RestClient
from opensearch_tpu.utils.metrics import (LatencyHistogram,
                                          MetricsRegistry, merge_sketches,
                                          render_prometheus,
                                          sketch_percentile,
                                          sketch_snapshot)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "prometheus_exposition.txt")


def _hist(name, values):
    h = LatencyHistogram(name)
    for v in values:
        h.record(float(v))
    return h


def _percentile_sweep(wire):
    bins = {int(b): int(c) for b, c in wire["bins"].items()}
    return [sketch_percentile(bins, wire["count"], p)
            for p in range(1, 101)]


# ----------------------------------------------------------------------
# DDSketch merge: the algebra fleet percentiles stand on
# ----------------------------------------------------------------------

class TestSketchMerge:
    def _streams(self):
        rng = np.random.default_rng(7)
        a = rng.lognormal(1.0, 1.2, size=400)
        b = rng.lognormal(3.0, 0.4, size=150)          # skewed differently
        c = rng.uniform(0.1, 5000.0, size=73)
        return a, b, c

    def test_merge_commutative(self):
        a, b, _ = self._streams()
        wa, wb = _hist("a", a).to_wire(), _hist("b", b).to_wire()
        assert merge_sketches([wa, wb]) == merge_sketches([wb, wa])

    def test_merge_associative(self):
        a, b, c = self._streams()
        wa, wb, wc = (_hist("a", a).to_wire(), _hist("b", b).to_wire(),
                      _hist("c", c).to_wire())
        left = merge_sketches([merge_sketches([wa, wb]), wc])
        right = merge_sketches([wa, merge_sketches([wb, wc])])
        assert left == right

    def test_union_parity_exact_nearest_rank(self):
        # the federation soundness property: a two-node merged sketch
        # answers EVERY nearest-rank percentile identically to a single
        # sketch fed the union stream — so fleet percentiles from
        # merged sketches equal a single-node oracle holding all data
        a, b, _ = self._streams()
        merged = merge_sketches([_hist("a", a).to_wire(),
                                 _hist("b", b).to_wire()])
        union = _hist("u", np.concatenate([a, b])).to_wire()
        assert merged["bins"] == union["bins"]
        assert merged["count"] == union["count"]
        assert merged["sum_ms"] == pytest.approx(union["sum_ms"],
                                                 rel=1e-9)
        assert _percentile_sweep(merged) == _percentile_sweep(union)

    def test_merge_wire_into_instance(self):
        a, b, _ = self._streams()
        ha = _hist("a", a)
        ha.merge_wire(_hist("b", b).to_wire())
        union = _hist("u", np.concatenate([a, b]))
        assert ha.to_wire()["bins"] == union.to_wire()["bins"]
        assert ha.snapshot() == union.snapshot()

    def test_merged_percentiles_differ_from_averaged(self):
        # the bug federation exists to avoid: averaging per-node p99s is
        # NOT the fleet p99 for skewed per-node distributions
        fast = _hist("fast", [1.0] * 1000)
        slow = _hist("slow", [500.0] * 100)
        avg_p99 = (fast.percentile(99) + slow.percentile(99)) / 2
        merged = merge_sketches([fast.to_wire(), slow.to_wire()])
        bins = {int(k): v for k, v in merged["bins"].items()}
        fleet_p99 = sketch_percentile(bins, merged["count"], 99)
        # 100/1100 requests at 500ms: the TRUE fleet p99 sits in the
        # slow node's tail; the averaged per-node p99 is a ~250ms
        # fiction in between
        assert fleet_p99 > 400.0
        assert avg_p99 < 0.6 * fleet_p99

    def test_empty_and_garbage_wires(self):
        w = merge_sketches([{}, None, {"bins": {}, "count": 0}])
        assert w == {"bins": {}, "count": 0, "sum_ms": 0.0}
        assert sketch_snapshot(w)["p99_ms"] is None


# ----------------------------------------------------------------------
# Prometheus exposition
# ----------------------------------------------------------------------

class TestPrometheusExposition:
    def _golden_registry(self):
        reg = MetricsRegistry()
        reg.counter("dist.rpc.failed").inc(3)
        reg.counter("fleet.scrapes").inc(42)
        reg.gauge("serving.queue_depth").set(7.5)
        reg.gauge("slo.interactive-latency-p99.burn_fast").set(0.25)
        h = reg.histogram("search.lane.interactive.latency")
        for v in (1.0, 2.5, 10.0, 100.0, 250.0):
            h.record(v)
        # ingest observatory series (ostpu_indexing_*): one of each
        # shape the write path emits — counter, extensive gauge, and the
        # refresh-to-visible sketch exported as a summary
        reg.counter("indexing.bulk.items").inc(120)
        reg.counter("indexing.refresh.total").inc(4)
        reg.gauge("indexing.buffer.bytes").set(16384.0)
        reg.gauge("indexing.merge.backlog").set(2.0)
        rtv = reg.histogram("indexing.refresh_to_visible_ms")
        for v in (12.0, 40.0, 95.0, 300.0):
            rtv.record(v)
        return reg

    def _golden_insights(self):
        # the bounded top-K query-shape export (obs/insights.py): shape
        # HASHES as labels, never query text — extending the golden file
        # pins the exposition shape AND the label discipline
        return [{"fingerprint": "a1b2c3d4e5f6", "count": 42,
                 "latency_sum_ms": 1234.5, "bytes_moved": 81920},
                {"fingerprint": "0f9e8d7c6b5a", "count": 7,
                 "latency_sum_ms": 77.25, "bytes_moved": 4096}]

    def test_golden_file(self):
        text = render_prometheus(self._golden_registry(), node="node-a",
                                 insights=self._golden_insights())
        with open(GOLDEN) as fh:
            assert text == fh.read()

    def test_help_type_pairs_for_every_sample(self):
        text = render_prometheus(self._golden_registry(), node="n",
                                 insights=self._golden_insights())
        lines = text.strip().splitlines()
        helps = {ln.split()[2] for ln in lines
                 if ln.startswith("# HELP")}
        types = {ln.split()[2] for ln in lines
                 if ln.startswith("# TYPE")}
        assert helps == types and len(helps) == 13
        # every sample line's metric (modulo _sum/_count suffix) has a
        # TYPE header
        for ln in lines:
            if ln.startswith("#"):
                continue
            name = ln.split("{")[0].split()[0]
            base = name
            for suf in ("_sum", "_count"):
                if base.endswith(suf) and base[: -len(suf)] in types:
                    base = base[: -len(suf)]
            assert base in types, ln

    def test_node_label_on_every_sample(self):
        text = render_prometheus(self._golden_registry(), node="node-a")
        for ln in text.strip().splitlines():
            if not ln.startswith("#"):
                assert 'node="node-a"' in ln, ln
        # and absent entirely without a node (back-compat single-node)
        bare = render_prometheus(self._golden_registry())
        assert "node=" not in bare
        assert 'quantile="0.5"' in bare

    def test_label_escaping_and_sanitization(self):
        reg = MetricsRegistry()
        reg.counter("weird.héllo-metric+x").inc(1)
        text = render_prometheus(reg, node='a"b\\c\nd')
        assert "ostpu_weird_h_llo_metric_x" in text
        assert 'node="a\\"b\\\\c\\nd"' in text
        # sanitization is per-character stable: distinct raw names that
        # differ only in WHICH separator keep distinct positions
        reg2 = MetricsRegistry()
        reg2.counter("a.b").inc(1)
        reg2.counter("a..b").inc(2)
        t2 = render_prometheus(reg2)
        assert "ostpu_a_b 1" in t2 and "ostpu_a__b 2" in t2


# ----------------------------------------------------------------------
# time-series retention
# ----------------------------------------------------------------------

class TestTimeSeries:
    def test_ring_bounded_and_rates(self):
        reg = MetricsRegistry()
        s = TimeSeriesSampler(registry=reg, interval_s=0.01, capacity=8)
        c = reg.counter("reqs")
        for i in range(20):
            c.inc(5)
            s.sample_once()
        assert s.stats()["samples"] == 8            # bounded ring
        h = s.history("reqs", window_s=1e9)
        assert len(h["points"]) == 8
        assert h["kind"] == "counter"
        # every adjacent delta is 5; rate positive
        vals = [p["value"] for p in h["points"]]
        assert all(b - a == 5 for a, b in zip(vals, vals[1:]))
        assert all(p["rate"] > 0 for p in h["points"][1:])

    def test_gauge_and_histogram_series(self):
        reg = MetricsRegistry()
        s = TimeSeriesSampler(registry=reg, interval_s=0.01, capacity=32)
        g = reg.gauge("depth")
        h = reg.histogram("lat")
        for i in range(4):
            g.set(i * 2.0)
            h.record(10.0 * (i + 1))
            s.sample_once()
        gh = s.history("depth", 1e9)
        assert gh["kind"] == "gauge"
        assert [p["value"] for p in gh["points"]] == [0.0, 2.0, 4.0, 6.0]
        hh = s.history("lat", 1e9)
        assert hh["kind"] == "histogram"
        assert [p["count"] for p in hh["points"]] == [1, 2, 3, 4]
        assert hh["points"][-1]["mean_ms"] == pytest.approx(40.0)

    def test_windowed_percentile_and_over_budget(self):
        reg = MetricsRegistry()
        s = TimeSeriesSampler(registry=reg, interval_s=0.01, capacity=64)
        s.track_histogram("lat")
        h = reg.histogram("lat")
        s.sample_once()
        for v in [10.0] * 90 + [1000.0] * 10:
            h.record(v)
        s.sample_once()
        p50 = s.window_percentile("lat", 1e9, 50)
        p99 = s.window_percentile("lat", 1e9, 99)
        assert p50 == pytest.approx(10.0, rel=0.01)
        assert p99 == pytest.approx(1000.0, rel=0.01)
        over, total = s.window_over_budget("lat", 1e9, 250.0)
        assert (over, total) == (10, 100)

    def test_counter_delta_clamped_and_sparse(self):
        reg = MetricsRegistry()
        s = TimeSeriesSampler(registry=reg, interval_s=0.01, capacity=16)
        s.sample_once()
        assert s.counter_delta("absent", 1e9) == 0.0
        c = reg.counter("x")
        c.inc(7)
        s.sample_once()
        c.set(2)                      # reset mid-window
        s.sample_once()
        assert s.counter_delta("x", 1e9) >= 0.0

    def test_thread_lifecycle(self):
        reg = MetricsRegistry()
        s = TimeSeriesSampler(registry=reg, interval_s=0.005, capacity=64)
        s.ensure_started()
        try:
            assert s.running
            deadline = time.monotonic() + 2.0
            while s.stats()["ticks"] < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert s.stats()["ticks"] >= 3
        finally:
            s.stop()
        assert not s.running

    def test_rest_history_surface(self):
        c = RestClient()
        c.node.timeseries.reset()
        from opensearch_tpu.utils.metrics import METRICS
        METRICS.counter("obs.test.reqs").inc(3)
        c.node.timeseries.sample_once()
        METRICS.counter("obs.test.reqs").inc(3)
        c.node.timeseries.sample_once()
        out = c.metrics_history("obs.test.reqs", 1e9)
        blk = out["nodes"][c.node.node_name]
        assert blk["metric"] == "obs.test.reqs"
        assert len(blk["points"]) == 2
        # and the _nodes/stats block reports the sampler
        ns = c.nodes_stats()["nodes"][c.node.node_name]
        assert ns["timeseries"]["samples"] >= 2
        assert "slo" in ns
        c.node.timeseries.reset()


# ----------------------------------------------------------------------
# SLO burn-rate engine
# ----------------------------------------------------------------------

class TestSLOEngine:
    def _rig(self, **slo_kw):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(registry=reg, interval_s=0.01,
                                    capacity=128)
        engine = SLOEngine(sampler=sampler, registry=reg)
        kw = dict(name="transport", kind="counter_ratio", target=0.95,
                  fast_window_s=60.0, slow_window_s=120.0,
                  bad_metrics=["rpc.failed"], total_metrics=["reqs"],
                  burn_threshold=2.0)
        kw.update(slo_kw)
        engine.arm([SLO(**kw)])
        return reg, sampler, engine

    def test_validation(self):
        with pytest.raises(ValueError):
            SLO("x", "latency", 0.99, fast_window_s=5, slow_window_s=30)
        with pytest.raises(ValueError):
            SLO("x", "nope", 0.99, fast_window_s=5, slow_window_s=30)
        with pytest.raises(ValueError):
            SLO("x", "error_rate", 1.5, fast_window_s=5, slow_window_s=30)
        with pytest.raises(ValueError):
            SLO("x", "error_rate", 0.99, fast_window_s=60,
                slow_window_s=5)          # fast > slow
        with pytest.raises(ValueError):
            SLO("x", "counter_ratio", 0.99, fast_window_s=5,
                slow_window_s=30)         # no metrics

    def test_burn_math_and_firing_edge(self):
        RECORDER.reset()
        reg, sampler, engine = self._rig()
        reg.counter("reqs").inc(100)
        sampler.sample_once()                   # baseline
        reg.counter("reqs").inc(100)
        reg.counter("rpc.failed").inc(20)       # 20% bad, budget 5%
        sampler.sample_once()                   # evaluation rides the tick
        st = engine.status()["status"]["transport"]
        assert st["state"] == "firing"
        assert st["fast"]["burn_rate"] == pytest.approx(0.2 / 0.05,
                                                        rel=0.01)
        assert reg.gauge("slo.transport.firing").value == 1.0
        assert reg.counter("slo.alerts_total").value == 1
        alerts = engine.status()["alerts"]
        assert len(alerts) == 1 and alerts[0]["slo"] == "transport"
        # edge-triggered: still burning on the next tick, no second alert
        reg.counter("reqs").inc(10)
        reg.counter("rpc.failed").inc(5)
        sampler.sample_once()
        assert engine.alerts_fired == 1
        engine.disarm()

    def test_firing_dumps_offending_series(self):
        RECORDER.reset()
        reg, sampler, engine = self._rig()
        reg.counter("reqs").inc(50)
        sampler.sample_once()
        reg.counter("rpc.failed").inc(50)
        reg.counter("reqs").inc(50)
        sampler.sample_once()
        assert engine.status()["status"]["transport"]["state"] == "firing"
        dumps = [d for d in RECORDER.dumps() if d["reason"] == "slo_burn"]
        assert dumps, "firing must freeze a flight-recorder dump"
        evs = [e for tl in dumps[0]["timelines"].values()
               for e in tl["events"] if e["kind"] == "slo.burn"]
        assert evs and evs[0]["slo"] == "transport"
        series = evs[0]["series"]
        # the offending window's series rides the event: both the bad
        # and the total metric, with the window's points
        assert set(series) == {"rpc.failed", "reqs"}
        # the bad counter was born mid-window: its series holds the
        # tick(s) since creation; the total metric holds the full window
        assert len(series["rpc.failed"]["points"]) >= 1
        assert len(series["reqs"]["points"]) == 2
        engine.disarm()
        RECORDER.reset()

    def test_resolution_when_burn_stops(self):
        reg, sampler, engine = self._rig(fast_window_s=0.05,
                                         slow_window_s=0.1)
        reg.counter("reqs").inc(10)
        sampler.sample_once()
        reg.counter("rpc.failed").inc(10)
        reg.counter("reqs").inc(10)
        sampler.sample_once()
        assert engine.status()["status"]["transport"]["state"] == "firing"
        # quiet traffic until the bad window ages out of BOTH windows
        deadline = time.monotonic() + 3.0
        state = "firing"
        while state == "firing" and time.monotonic() < deadline:
            time.sleep(0.06)
            reg.counter("reqs").inc(10)
            sampler.sample_once()
            state = engine.status()["status"]["transport"]["state"]
        assert state == "ok"
        assert reg.gauge("slo.transport.firing").value == 0.0
        engine.disarm()

    def test_refire_cooldown_stamp_only_moves_on_real_alerts(self):
        # a flapping SLO must be rate-limited, not silenced: a
        # suppressed firing edge must NOT advance the cooldown stamp
        reg, sampler, engine = self._rig(fast_window_s=0.05,
                                         slow_window_s=0.1)
        reg.counter("reqs").inc(10)
        sampler.sample_once()
        reg.counter("rpc.failed").inc(10)
        reg.counter("reqs").inc(10)
        sampler.sample_once()
        assert engine.alerts_fired == 1
        lf1 = engine.status()["status"]["transport"]["last_fired_mono"]
        # quiet until resolved
        deadline = time.monotonic() + 3.0
        while (engine.status()["status"]["transport"]["state"] == "firing"
               and time.monotonic() < deadline):
            time.sleep(0.06)
            reg.counter("reqs").inc(10)
            sampler.sample_once()
        assert engine.status()["status"]["transport"]["state"] == "ok"
        # burn again inside the 30s cooldown: edge suppressed, and the
        # stamp must still point at the REAL alert
        reg.counter("rpc.failed").inc(10)
        reg.counter("reqs").inc(10)
        sampler.sample_once()
        st = engine.status()["status"]["transport"]
        assert st["state"] == "firing"
        assert engine.alerts_fired == 1
        assert st["last_fired_mono"] == lf1
        engine.disarm()

    def test_latency_slo_over_budget(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(registry=reg, interval_s=0.01,
                                    capacity=64)
        engine = SLOEngine(sampler=sampler, registry=reg)
        engine.arm([SLO("p99", "latency", target=0.9,
                        fast_window_s=60.0, slow_window_s=120.0,
                        latency_budget_ms=100.0, burn_threshold=2.0)])
        h = reg.histogram("search.lane.interactive.latency_ms")
        sampler.sample_once()
        for v in [10.0] * 5 + [500.0] * 5:       # 50% over budget
            h.record(v)
        sampler.sample_once()
        st = engine.status()["status"]["p99"]
        assert st["state"] == "firing"
        assert st["fast"]["bad"] == 5 and st["fast"]["total"] == 10
        engine.disarm()

    def test_default_slos_and_min_events(self):
        slos = default_slos(fast_window_s=5.0, slow_window_s=30.0)
        assert {s.kind for s in slos} == {"latency", "error_rate",
                                          "availability",
                                          "rejection_rate"}
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(registry=reg, interval_s=0.01,
                                    capacity=64)
        engine = SLOEngine(sampler=sampler, registry=reg)
        engine.arm(slos)
        # no traffic at all: nothing fires, every state ok
        sampler.sample_once()
        sampler.sample_once()
        assert all(st["state"] == "ok"
                   for st in engine.status()["status"].values())
        engine.disarm()

    def test_slo_rest_surface(self):
        c = RestClient()
        out = c.slo_status()
        assert out["armed"] in (True, False)
        assert "slos" in out and "alerts" in out


# ----------------------------------------------------------------------
# federation over a live 2-node cluster
# ----------------------------------------------------------------------

def _get(addr, path, text=False, timeout=15):
    with urllib.request.urlopen(f"http://{addr}{path}",
                                timeout=timeout) as r:
        raw = r.read().decode()
    return raw if text else json.loads(raw)


MAPPING = {"settings": {"number_of_shards": 2},
           "mappings": {"properties": {"body": {"type": "text"}}}}


@pytest.fixture()
def cluster():
    from opensearch_tpu.cluster.distnode import DistClusterNode
    a = DistClusterNode("fa")
    b = DistClusterNode("fb", seed=a.addr)
    a.create_index("fidx", MAPPING)
    rng = np.random.default_rng(5)
    words = ["alpha", "beta", "gamma", "delta"]
    for i in range(40):
        a.index_doc("fidx", {"body": " ".join(
            rng.choice(words, size=int(rng.integers(2, 5))))}, id=str(i))
    a.refresh("fidx")
    try:
        yield a, b
    finally:
        a.stop()
        try:
            b.stop()
        except Exception:       # noqa: BLE001 — already stopped by a test
            pass


class TestFleetFederation:
    def test_cluster_stats_merged_sketches_match_union_oracle(self,
                                                              cluster):
        a, b = cluster
        # inject DISJOINT per-node registries (the one-node-per-process
        # deployment shape): each node's sketch holds its own stream,
        # and the fleet percentiles must equal a single-node oracle fed
        # the union of samples
        rng = np.random.default_rng(11)
        sa = rng.lognormal(1.0, 1.0, 300)
        sb = rng.lognormal(4.0, 0.5, 80)
        ra, rb = MetricsRegistry(), MetricsRegistry()
        for v in sa:
            ra.histogram("lat").record(float(v))
        for v in sb:
            rb.histogram("lat").record(float(v))
        ra.counter("served").inc(300)
        rb.counter("served").inc(80)
        ra.gauge("depth").set(3.0)
        rb.gauge("depth").set(9.0)
        a.obs_registry, b.obs_registry = ra, rb
        cs = a.cluster_stats()
        assert cs["_nodes"] == {"total": 2, "successful": 2, "failed": 0}
        # counters SUM
        assert cs["counters"]["served"] == 380
        # gauges roll up PER NODE, never summed
        assert cs["nodes"]["fa"]["gauges"]["depth"] == 3.0
        assert cs["nodes"]["fb"]["gauges"]["depth"] == 9.0
        assert "depth" not in cs["counters"]
        # fleet percentiles == single-node oracle over the union
        oracle = _hist("u", np.concatenate([sa, sb]))
        assert cs["percentiles"]["lat"] == oracle.snapshot()
        assert (_percentile_sweep(cs["histograms"]["lat"])
                == _percentile_sweep(oracle.to_wire()))

    def test_any_member_coordinates_and_shapes_agree(self, cluster):
        a, b = cluster
        ra, rb = MetricsRegistry(), MetricsRegistry()
        ra.counter("c").inc(1)
        rb.counter("c").inc(2)
        a.obs_registry, b.obs_registry = ra, rb
        ca = a.cluster_stats()
        cb = b.cluster_stats()
        assert ca["counters"] == cb["counters"] == {"c": 3}
        assert ca["coordinator"] == "fa" and cb["coordinator"] == "fb"

    def test_nodes_stats_fanout_over_http(self, cluster):
        a, _b = cluster
        ns = _get(a.addr, "/_nodes/stats")
        assert sorted(ns["nodes"]) == ["fa", "fb"]
        assert ns["_nodes"]["failed"] == 0
        for blk in ns["nodes"].values():
            assert "telemetry" in blk and "serving" in blk
        # the {id} filter targets one member, unknown ids are a 404 —
        # never a silent whole-fleet answer
        one = _get(a.addr, "/_nodes/fb/stats")
        assert sorted(one["nodes"]) == ["fb"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(a.addr, "/_nodes/ghost/stats")
        assert ei.value.code == 404
        # single-node /_cluster/stats serves the same schema (fleet of 1)
        solo = RestClient().cluster_stats()
        assert solo["_nodes"]["total"] == 1
        assert set(solo) == set(_get(a.addr, "/_cluster/stats"))

    def test_hot_threads_fanout(self, cluster):
        a, _b = cluster
        text = _get(a.addr, "/_nodes/hot_threads", text=True)
        assert "::: {fa}" in text and "::: {fb}" in text
        j = _get(a.addr, "/_nodes/fb/hot_threads?format=json")
        assert sorted(j["nodes"]) == ["fb"]
        assert j["nodes"]["fb"]["threads"], "remote sampled no threads"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(a.addr, "/_nodes/nope/hot_threads")
        assert ei.value.code == 404

    def test_history_fanout(self, cluster):
        a, _b = cluster
        from opensearch_tpu.obs.timeseries import SAMPLER
        from opensearch_tpu.utils.metrics import METRICS
        METRICS.counter("fed.test.counter").inc(1)
        SAMPLER.sample_once()
        METRICS.counter("fed.test.counter").inc(1)
        SAMPLER.sample_once()
        h = _get(a.addr,
                 "/_nodes/stats/history?metric=fed.test.counter"
                 "&window=3600")
        assert h["_nodes"]["successful"] == 2
        for blk in h["nodes"].values():
            assert blk["metric"] == "fed.test.counter"
            assert len(blk["points"]) >= 2
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(a.addr, "/_nodes/stats/history")       # metric required
        assert ei.value.code == 400
        SAMPLER.reset()

    def test_dead_member_degrades_honestly(self, cluster):
        a, b = cluster
        b.stop()
        t0 = time.monotonic()
        cs = a.cluster_stats()
        took = time.monotonic() - t0
        assert cs["_nodes"] == {"total": 2, "successful": 1, "failed": 1}
        assert cs["nodes"]["fb"]["status"] == "failed"
        assert "error" in cs["nodes"]["fb"]
        # a dead member must never stall the coordinator (scrape cap)
        assert took < 10.0
        ns = _get(a.addr, "/_nodes/stats")
        assert ns["_nodes"]["failed"] == 1
        assert "failed" in ns["nodes"]["fb"]
        text = _get(a.addr, "/_nodes/hot_threads", text=True)
        assert "::: {fa}" in text and "scrape failed" in text


class TestChaosDetection:
    def test_burn_alert_fires_under_seeded_chaos(self):
        """The acceptance loop in miniature: seeded chaos kills a member's RPC
        plane, replica failover keeps pages identical — and the SLO
        engine now DETECTS the event within the fast window, dumping
        the offending window's series."""
        from opensearch_tpu.cluster import faults
        from opensearch_tpu.cluster.distnode import (DistClusterNode,
                                                     RetryPolicy)
        from opensearch_tpu.obs.timeseries import SAMPLER
        from opensearch_tpu.utils.metrics import METRICS
        RECORDER.reset()
        SAMPLER.reset()
        policy = RetryPolicy(same_member_retries=1, budget=4,
                             base_backoff_s=0.001, max_backoff_s=0.004)
        a = DistClusterNode("ca", retry_policy=policy)
        b = DistClusterNode("cb", seed=a.addr)
        engine = SLOEngine(sampler=SAMPLER, registry=METRICS)
        try:
            a.create_index("cidx", {
                "settings": {"number_of_shards": 4,
                             "number_of_node_replicas": 1},
                "mappings": {"properties": {"body": {"type": "text"}}}})
            for i in range(30):
                a.index_doc("cidx", {"body": f"alpha beta w{i % 7}"},
                            id=str(i))
            a.refresh("cidx")
            body = {"query": {"match": {"body": "alpha"}}, "size": 5}
            baseline = a.search("cidx", dict(body))
            engine.arm([SLO(
                "transport-health", "counter_ratio", target=0.95,
                fast_window_s=5.0, slow_window_s=30.0,
                bad_metrics=["dist.rpc.failed",
                             "dist.deadline.exhausted"],
                total_metrics=["search.lane.interactive.requests"],
                burn_threshold=2.0)])
            SAMPLER.sample_once()
            t_chaos = time.monotonic()
            faults.install(faults.ChaosSchedule(seed=3).kill_node("cb"))
            try:
                for _ in range(6):
                    r = a.search("cidx", dict(body))
                    # replica failover: pages stay byte-identical with
                    # zero failed shards even while the victim is dark
                    assert r["_shards"]["failed"] == 0
                    assert r["hits"] == baseline["hits"]
                    SAMPLER.sample_once()
            finally:
                faults.uninstall()
                a.member_fd.note_success("cb")
            st = engine.status()
            assert st["status"]["transport-health"]["state"] == "firing"
            assert st["alerts"], "burn alert must have fired"
            fired_at = st["alerts"][0]["at_mono"]
            # detected within the fast window of the chaos starting
            assert fired_at - t_chaos < 5.0
            dumps = [d for d in RECORDER.dumps()
                     if d["reason"] == "slo_burn"]
            assert dumps
            evs = [e for tl in dumps[0]["timelines"].values()
                   for e in tl["events"] if e["kind"] == "slo.burn"]
            assert evs and "dist.rpc.failed" in evs[0]["series"]
        finally:
            engine.disarm()
            SAMPLER.reset()
            RECORDER.reset()
            a.stop()
            b.stop()


class TestFederationErrors:
    def test_single_node_foreign_hot_threads_404(self):
        from opensearch_tpu.rest.http_server import HttpServer
        srv = HttpServer(RestClient())
        port = srv.start()
        try:
            out = _get(f"127.0.0.1:{port}",
                       "/_nodes/node-0/hot_threads?format=json")
            assert isinstance(out, list)      # own name resolves locally
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"127.0.0.1:{port}", "/_nodes/ghost/hot_threads")
            assert ei.value.code == 404
        finally:
            srv.stop()
