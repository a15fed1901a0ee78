"""HBM ledger + per-query device cost accounting (ISSUE 7).

Covers: exact concurrent charge/release balance, weakref-finalize release
exactness under forced GC, the partial→full residency promotion dedupe
(the `pruned_arrays` double-charge bugfix), breaker-trip behavior,
residency events on flight-recorder timelines, the `_cat/segments` and
`_nodes/stats` "hbm" surfaces, the profile `cost` block against a
hand-computed oracle, the `explain=device_plan` view, and the
`scripts/hbm_report.py` smoke. The standing ledger↔breaker invariant
(`sum(live charged bytes) == breaker.used`) is asserted after EVERY
tier-1 test by the conftest autouse fixture."""

import gc
import json
import threading

import numpy as np
import pytest

from opensearch_tpu.cluster.node import Node
from opensearch_tpu.obs import query_cost
from opensearch_tpu.obs.flight_recorder import RECORDER
from opensearch_tpu.obs.hbm_ledger import LEDGER, HBMLedger
from opensearch_tpu.rest.client import RestClient
from opensearch_tpu.utils.breaker import (CircuitBreaker,
                                          CircuitBreakingException)


@pytest.fixture
def scratch_breaker():
    """Fresh breaker installed as the ledger's charge target; restores
    the previous target afterwards (the LEDGER is a process singleton)."""
    old = LEDGER.breaker
    b = CircuitBreaker("scratch", 1 << 40)
    LEDGER.set_breaker(b)
    try:
        yield b
    finally:
        LEDGER.set_breaker(old)


def make_client():
    c = RestClient(node=Node(mesh_service=False))
    c.indices.create("hbmt", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"body": {"type": "text"},
                                    "status": {"type": "keyword"}}}})
    return c


# ---------------------------------------------------------------------
# core ledger mechanics
# ---------------------------------------------------------------------

class TestLedgerCore:
    def test_register_release_exact_balance(self, scratch_breaker):
        a = LEDGER.register("aligned_postings", 1000, label="t1")
        b = LEDGER.register("filter_list", 24, label="t2")
        assert scratch_breaker.used == 1024
        assert not LEDGER.verify_breakers()
        LEDGER.release(a)
        assert scratch_breaker.used == 24
        LEDGER.release(b)
        LEDGER.release(b)          # idempotent: double release is a no-op
        assert scratch_breaker.used == 0
        assert not LEDGER.verify_breakers()

    def test_concurrent_hammer_exact_final_balance(self, scratch_breaker):
        """32 threads register/release concurrently; the final balance is
        exactly zero on both the ledger side and the derived breaker."""
        NT, PER = 32, 100
        # other test modules may legitimately keep segments (and their
        # cached filtered-postings tenants) alive in module globals —
        # assert this hammer's own balance, not a global absolute zero,
        # so the test doesn't depend on file execution order
        base = LEDGER.snapshot()["tenants"].get("filtered_postings",
                                                {}).get("bytes", 0)
        errs = []

        def worker(tid):
            try:
                held = []
                for i in range(PER):
                    alloc = LEDGER.register(
                        "filtered_postings", 64 + (tid * PER + i) % 512,
                        label=f"h{tid}-{i}")
                    if i % 3 == 0:
                        LEDGER.release(alloc)
                    else:
                        held.append(alloc)
                for alloc in held:
                    LEDGER.release(alloc)
            except Exception as e:            # noqa: BLE001
                errs.append(repr(e))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(NT)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert scratch_breaker.used == 0
        snap = LEDGER.snapshot()
        assert snap["tenants"].get("filtered_postings",
                                   {}).get("bytes", 0) == base
        assert not LEDGER.verify_breakers()

    def test_weakref_finalize_releases_exactly_once(self, scratch_breaker):
        class Owner:
            pass

        o = Owner()
        alloc = LEDGER.register("quality_tier", 4096, owner=o, label="gc")
        assert scratch_breaker.used == 4096
        del o
        gc.collect()
        assert scratch_breaker.used == 0
        # the finalizer already fired; an explicit release stays a no-op
        LEDGER.release(alloc)
        assert scratch_breaker.used == 0

    def test_explicit_release_then_owner_gc_no_double_credit(
            self, scratch_breaker):
        class Owner:
            pass

        o = Owner()
        pad = LEDGER.register("filter_list", 500, label="pad")
        alloc = LEDGER.register("quality_tier", 100, owner=o)
        LEDGER.release(alloc)
        assert scratch_breaker.used == 500
        del o
        gc.collect()               # finalizer fires; must not re-credit
        assert scratch_breaker.used == 500
        LEDGER.release(pad)

    def test_breaker_trip_records_nothing(self, scratch_breaker):
        tiny = CircuitBreaker("tiny", 100)
        LEDGER.set_breaker(tiny)
        before = LEDGER.snapshot()["total_bytes"]
        with pytest.raises(CircuitBreakingException):
            LEDGER.register("segment_columns", 1 << 20, label="boom")
        assert tiny.used == 0
        assert LEDGER.snapshot()["total_bytes"] == before
        assert not LEDGER.verify_breakers()

    def test_peak_tracking_survives_release(self, scratch_breaker):
        led = HBMLedger()          # isolated instance: deterministic peaks
        led.set_breaker(scratch_breaker)
        a = led.register("aligned_postings", 1 << 20)
        b = led.register("aligned_postings", 1 << 20)
        led.release(a)
        led.release(b)
        snap = led.snapshot()
        assert snap["total_bytes"] == 0
        assert snap["peak_bytes"] == 2 << 20
        assert snap["tenants"]["aligned_postings"]["peak_bytes"] == 2 << 20

    def test_uncharged_advisory_tenant(self, scratch_breaker):
        alloc = LEDGER.register("program", 0, charge=False, label="adv")
        assert scratch_breaker.used == 0
        snap = LEDGER.snapshot()
        assert snap["tenants"]["program"]["count"] >= 1
        LEDGER.release(alloc)


# ---------------------------------------------------------------------
# partial→full promotion dedupe (the satellite bugfix)
# ---------------------------------------------------------------------

class TestPartialPromotion:
    def test_partial_charges_released_on_full_build(self):
        c = make_client()
        for i in range(40):
            c.index("hbmt", {"body": f"alpha w{i}", "status": "draft"},
                    id=str(i))
        c.indices.refresh("hbmt")
        seg = c.node.indices["hbmt"].shards[0].segments[0]
        breaker = c.node.breakers.breaker("fielddata")
        used0 = breaker.used

        # partial residency first (the filter-mask path's entry point)
        seg.pruned_arrays(None, {"postings": {"status"},
                                 "keyword": {"status"}})
        partial_allocs = dict(seg.__dict__.get("_field_device_allocs", {}))
        assert partial_allocs, "partial build registered nothing"
        partial_bytes = sum(a.nbytes for a in partial_allocs.values())
        assert partial_bytes > 0
        assert breaker.used == used0 + partial_bytes

        # full-residency promotion: the partial charges must be released,
        # NOT stacked on top of the full pytree's charge (the
        # "later full device_arrays() reuses nothing" double-charge)
        seg.device_arrays(None)
        # codec v2 splits the full build across per-kind allocations
        # (segment_columns + impact_postings + advisory block_max)
        full_bytes = sum(a.nbytes for a in
                         seg.__dict__["_hbm_allocs"][None] if a.charged)
        assert breaker.used == used0 + full_bytes
        assert not any(k[0] is None for k in
                       seg.__dict__.get("_field_device_allocs", {}))
        assert all(not a.live for a in partial_allocs.values())
        # and pruned_arrays now serves from the full pytree, charging
        # nothing new
        seg.pruned_arrays(None, {"postings": {"status"}})
        assert breaker.used == used0 + full_bytes
        assert not LEDGER.verify_breakers()

    def test_drop_device_releases_eagerly(self):
        c = make_client()
        for i in range(10):
            c.index("hbmt", {"body": f"beta w{i}"}, id=str(i))
        c.indices.refresh("hbmt")
        seg = c.node.indices["hbmt"].shards[0].segments[0]
        breaker = c.node.breakers.breaker("fielddata")
        used0 = breaker.used
        seg.device_arrays(None)
        assert breaker.used > used0
        seg.drop_device()
        assert breaker.used == used0


# ---------------------------------------------------------------------
# end-to-end surfaces
# ---------------------------------------------------------------------

class TestSurfaces:
    def test_residency_events_on_timeline(self):
        c = make_client()
        for i in range(12):
            c.index("hbmt", {"body": f"gamma delta w{i}"}, id=str(i))
        c.indices.refresh("hbmt")
        enabled0 = RECORDER.enabled
        RECORDER.enabled = True
        try:
            # fresh segment: the search triggers the device_arrays build
            # inside the request timeline -> hbm.build lands on it
            c.search("hbmt", {"query": {"match": {"body": "gamma"}}})
            dump = c.flight_recorder_dump(note="hbm-test")["dump"]
        finally:
            RECORDER.enabled = enabled0
        kinds = [ev.get("kind")
                 for tl in dump["timelines"].values()
                 for ev in tl["events"]]
        assert "hbm.build" in kinds
        builds = [ev for tl in dump["timelines"].values()
                  for ev in tl["events"] if ev.get("kind") == "hbm.build"]
        assert any(ev.get("tenant") == "segment_columns"
                   and ev.get("bytes", 0) > 0 for ev in builds)

    def test_nodes_stats_hbm_block_and_cat_segments(self):
        c = make_client()
        for i in range(15):
            c.index("hbmt", {"body": f"epsilon w{i}"}, id=str(i))
        c.indices.refresh("hbmt")
        c.search("hbmt", {"query": {"match": {"body": "epsilon"}}})
        hbm = c.nodes_stats()["nodes"]["node-0"]["hbm"]
        assert hbm["total_bytes"] > 0
        assert hbm["charged_bytes"] <= hbm["total_bytes"] or \
            hbm["charged_bytes"] == hbm["total_bytes"]
        assert "segment_columns" in hbm["tenants"]
        rows = c.cat.segments("hbmt")
        assert rows
        row = rows[0]
        assert int(row["memory.device"]) > 0
        assert "segment_columns=" in row["memory.device.tenants"]

    def test_ledger_matches_breaker_stats(self):
        c = make_client()
        for i in range(8):
            c.index("hbmt", {"body": f"zeta w{i}"}, id=str(i))
        c.indices.refresh("hbmt")
        c.search("hbmt", {"query": {"match": {"body": "zeta"}}})
        assert not LEDGER.verify_breakers()


# ---------------------------------------------------------------------
# per-query cost accounting
# ---------------------------------------------------------------------

class TestQueryCost:
    def _fixed_corpus(self):
        """Known synthetic segment: hand-computable document frequencies
        for the 3-term oracle — df(alpha)=3, df(beta)=3, df(gamma)=2."""
        c = make_client()
        docs = ["alpha beta gamma", "alpha beta", "beta gamma delta",
                "alpha", "delta epsilon"]
        for i, d in enumerate(docs):
            c.index("hbmt", {"body": d}, id=str(i))
        c.indices.refresh("hbmt")
        return c

    def test_profile_cost_matches_hand_computed_oracle(self):
        c = self._fixed_corpus()
        r = c.search("hbmt", {"query": {"match": {
            "body": "alpha beta gamma"}}, "profile": True})
        cost = r["profile"]["cost"]
        # predicted, from CSR stats alone: (3 + 3 + 2) true postings,
        # 6 bytes per codec-v2 slot (doc_id i32 + u16 quantized impact)
        assert cost["predicted_bytes_gathered"] == 8 * 6
        assert cost["predicted_scatter_adds"] == 8
        # actual, from the launched program shape: the eager impact pass
        # (search/impactpath.py) reads one row of IMPACT_BLOCK = 128 slots
        # a kept block slot, each as the two 128-posting plane rows its
        # window lies in: 3 kept blocks in the floor of 8 block slots =
        # 2 x 1,024 slots of 6 bytes; the scatter count is the TRUE kept
        # posting count
        assert cost["actual_bytes_gathered"] == 2 * 8 * 128 * 6
        assert cost["actual_scatter_adds"] == 8
        assert cost["launches"] == 1
        assert cost["predicted_vs_actual_pct"] == pytest.approx(
            100.0 * 48 / 12288, abs=0.01)

    def test_profile_cost_v1_oracle(self):
        """The legacy codec (what a commit from before the format rev
        loads as) keeps the 8-byte slot model and the XLA bucket-gather
        actuals."""
        c = self._fixed_corpus()
        for sh in c.node.indices["hbmt"].shards:
            for seg in sh.segments:
                seg.drop_impacts()
        r = c.search("hbmt", {"query": {"match": {
            "body": "alpha beta gamma"}}, "profile": True})
        cost = r["profile"]["cost"]
        assert cost["predicted_bytes_gathered"] == 8 * 8
        assert cost["predicted_scatter_adds"] == 8
        assert cost["actual_bytes_gathered"] == 256 * 8
        assert cost["actual_scatter_adds"] == 256
        assert cost["launches"] == 1
        assert cost["predicted_vs_actual_pct"] == pytest.approx(
            100.0 * 64 / 2048, abs=0.01)

    def test_device_plan_explain_view(self):
        c = self._fixed_corpus()
        r = c.search("hbmt", {"query": {"match": {"body": "alpha beta"}},
                              "explain": "device_plan"})
        plan = r["device_plan"]
        # 6 postings x 6-byte codec-v2 slots
        assert plan["cost"]["predicted_bytes_gathered"] == 6 * 6
        segs = plan["segments"]
        assert any("predicted_bytes_gathered" in e for e in segs)
        assert any(e.get("path") in ("xla", "impact") for e in segs)
        # device_plan must not attach per-hit _explanation trees
        assert all("_explanation" not in h for h in r["hits"]["hits"])

    def test_cost_histograms_recorded(self):
        from opensearch_tpu.utils.metrics import METRICS
        c = self._fixed_corpus()
        c.search("hbmt", {"query": {"match": {"body": "alpha"}}})
        hists = METRICS.snapshot()["histograms"]
        assert hists.get("cost.bytes_per_query", {}).get("count", 0) >= 1
        assert hists.get("cost.predicted_bytes_per_query",
                         {}).get("count", 0) >= 1

    def test_cost_disabled_env(self, monkeypatch):
        monkeypatch.setenv("OPENSEARCH_TPU_COST", "0")
        c = self._fixed_corpus()
        r = c.search("hbmt", {"query": {"match": {"body": "alpha"}},
                              "profile": True})
        assert "cost" not in r["profile"]

    def test_spec_gather_shape_walker(self):
        # query spec: nid int in slot 1, bucket in slot 4
        spec = ("bool", 0,
                (("terms", 1, "body", 8, 512, 0, 1.2, 0.75, "score"),),
                (), (), ())
        b, s = query_cost.spec_gather_shape(spec)
        assert (b, s) == (512 * 8, 512)
        # agg-shaped "terms" spec (string prefix in slot 1) is NOT counted
        agg = ("terms", "a0", "status", 64, ())
        assert query_cost.spec_gather_shape(agg) == (0, 0)


# ---------------------------------------------------------------------
# hbm_report smoke (CI/tooling satellite)
# ---------------------------------------------------------------------

class TestHbmReport:
    def test_report_smoke(self, capsys):
        import importlib
        H = importlib.import_module("scripts.hbm_report")
        rc = H.main(["--ndocs", "120"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "HBM ledger:" in out
        assert "segment_columns" in out
        assert "bytes/query" in out

    def test_report_json_shape(self, tmp_path):
        import importlib
        H = importlib.import_module("scripts.hbm_report")
        qf = tmp_path / "q.jsonl"
        qf.write_text(json.dumps(
            {"query": {"match": {"body": "w00000"}}, "size": 5}) + "\n")
        rep = H.build_report(100, queries_path=str(qf))
        assert rep["queries_replayed"] == 1
        assert rep["ledger"]["total_bytes"] > 0
        assert rep["per_query_costs"] and \
            rep["per_query_costs"][0]["actual_bytes_gathered"] > 0


class TestPressureEviction:
    """ROADMAP item 2: loading past the HBM budget must EVICT the
    least-recently-used segment planes and succeed, not fail — a 1M+ doc
    index's residency is budget-bounded, not load-bounded."""

    def _mk(self, name, n=300):
        from opensearch_tpu.index.mappings import Mappings
        from opensearch_tpu.index.segment import build_segment
        m = Mappings({"properties": {"body": {"type": "text"}}})
        docs = [m.parse(f"{name}{i}", {"body": "alpha beta gamma delta"})
                for i in range(n)]
        return build_segment(name, docs, m)

    @staticmethod
    def _one_bytes(s):
        """One segment's full device footprint, measured as a ledger
        DELTA: earlier tests' segments may still be resident (charged to
        their own nodes' breakers), so the absolute total would inflate
        the eviction budget and the breaker would never trip."""
        gc.collect()               # flush pending weakref releases first
        before = LEDGER.total_bytes()
        s.device_arrays()
        one = LEDGER.total_bytes() - before
        s.drop_device()
        return one

    def test_load_past_budget_evicts_lru_and_succeeds(self):
        s1, s2, s3 = self._mk("ev_a"), self._mk("ev_b"), self._mk("ev_c")
        one = self._one_bytes(s1)
        old = LEDGER.breaker
        br = CircuitBreaker("evict-test", int(one * 2.5))
        LEDGER.set_breaker(br)
        try:
            base_ev = LEDGER.pressure_evictions
            s1.device_arrays()
            s2.device_arrays()          # both fit
            # regression: this used to raise CircuitBreakingException —
            # now the LRU plane group (s1: loaded first, never re-used)
            # is evicted and the load proceeds
            s3.device_arrays()
            assert LEDGER.pressure_evictions == base_ev + 1
            assert not s1._device_cache          # the LRU victim
            assert s2._device_cache and s3._device_cache
            # the evicted segment transparently rebuilds on next use
            # (evicting the new LRU, s2)
            s1.device_arrays()
            assert LEDGER.pressure_evictions == base_ev + 2
            assert not s2._device_cache
            assert not LEDGER.verify_breakers()
        finally:
            LEDGER.set_breaker(old)
            for s in (s1, s2, s3):
                s.drop_device()

    def test_recency_touch_orders_victims(self):
        s1, s2, s3 = self._mk("tr_a"), self._mk("tr_b"), self._mk("tr_c")
        one = self._one_bytes(s1)
        old = LEDGER.breaker
        br = CircuitBreaker("touch-test", int(one * 2.5))
        LEDGER.set_breaker(br)
        try:
            s1.device_arrays()
            s2.device_arrays()
            s1.device_arrays()          # touch s1: s2 becomes LRU
            s3.device_arrays()
            assert s1._device_cache and not s2._device_cache
        finally:
            LEDGER.set_breaker(old)
            for s in (s1, s2, s3):
                s.drop_device()

    def test_eviction_skips_segment_mid_build(self):
        s1, s2 = self._mk("mb_a"), self._mk("mb_b")
        one = self._one_bytes(s1)
        old = LEDGER.breaker
        br = CircuitBreaker("busy-test", int(one * 1.5))
        LEDGER.set_breaker(br)
        try:
            s1.device_arrays()
            # hold s1's build lock: the evictor must refuse it and, with
            # nothing else evictable, the breaker exception propagates
            lock = s1.__dict__["_device_build_lock"]
            assert lock.acquire(blocking=False)
            try:
                with pytest.raises(CircuitBreakingException):
                    s2.device_arrays()
            finally:
                lock.release()
            # lock released: the same load now evicts s1 and succeeds
            s2.device_arrays()
            assert not s1._device_cache and s2._device_cache
            assert not LEDGER.verify_breakers()
        finally:
            LEDGER.set_breaker(old)
            for s in (s1, s2):
                s.drop_device()

    def test_evict_pressure_event_on_recorder_timeline(self):
        from opensearch_tpu.obs import flight_recorder as fr
        s1, s2 = self._mk("rc_a"), self._mk("rc_b")
        one = self._one_bytes(s1)
        old = LEDGER.breaker
        br = CircuitBreaker("rec-test", int(one * 1.5))
        LEDGER.set_breaker(br)
        was_enabled = fr.RECORDER.enabled
        fr.RECORDER.enabled = True
        tl = fr.RECORDER.start("search", test="evict")
        tok = fr.set_current(tl)
        try:
            s1.device_arrays()
            s2.device_arrays()
            events = [e for e in fr.RECORDER.timeline_events(tl)
                      if e.get("kind") == "hbm.evict_pressure"]
            assert events and events[0]["segment"] == "rc_a"
            assert events[0]["bytes"] > 0
        finally:
            fr.reset_current(tok)
            fr.RECORDER.enabled = was_enabled
            LEDGER.set_breaker(old)
            for s in (s1, s2):
                s.drop_device()


class TestTouchCleanup:
    """Code-review regression: `_touch` recency keys must not outlive
    their (segment, device) plane group — merge/refresh churn mints a new
    uid per merge, so retained keys leak in the process singleton."""

    def _mk(self, name, n=120):
        from opensearch_tpu.index.mappings import Mappings
        from opensearch_tpu.index.segment import build_segment
        m = Mappings({"properties": {"body": {"type": "text"}}})
        docs = [m.parse(f"{name}{i}", {"body": "alpha beta gamma"})
                for i in range(n)]
        return build_segment(name, docs, m)

    def test_drop_device_removes_touch_key(self):
        s = self._mk("tk_a")
        s.device_arrays()
        key = (s.uid, "default")
        assert any(k[0] == s.uid for k in LEDGER._touch)
        s.drop_device()
        gc.collect()        # flush any weakref finalizer releases
        assert not any(k[0] == s.uid for k in LEDGER._touch), key

    def test_gc_of_segment_removes_touch_key(self):
        s = self._mk("tk_b")
        s.device_arrays()
        uid = s.uid
        del s
        gc.collect()
        assert not any(k[0] == uid for k in LEDGER._touch)

    def test_failed_build_cleans_touch_key(self, scratch_breaker):
        """A build that trips the breaker with nothing evictable never
        registered an allocation, so the release-side cleanup can't fire
        — the register failure path must drop the pre-build touch key or
        sustained pressure leaks one entry per failed build (code-review
        regression)."""
        from opensearch_tpu.utils.breaker import (CircuitBreaker,
                                                  CircuitBreakingException)
        tiny = CircuitBreaker("tiny", 1)       # nothing fits, nothing to evict
        old = LEDGER.breaker
        LEDGER.set_breaker(tiny)
        try:
            s = self._mk("tk_fail")
            with pytest.raises(CircuitBreakingException):
                s.device_arrays()
            assert not any(k[0] == s.uid for k in LEDGER._touch)
        finally:
            LEDGER.set_breaker(old)
