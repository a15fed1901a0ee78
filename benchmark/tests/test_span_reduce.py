"""The reduction from the program's spans on a profiler trace to per-layer
numbers: on hand-made events (self times, the layer table, idle time
apportioned across spans and a request boundary) and on a small trace
recorded on one v5e with the spans in it (`data/*spans*.xplane.pb.gz`,
`record_trace.py`)."""

import glob
import gzip
import os

import pytest

import run
import span_reduce as sr
import trace_reduce as tr

MS = 1_000_000      # ns
R = tr.REQUEST
NEW = ["rest_ms_per_query", "coordinator_ms_per_query", "plan_ms_per_query",
       "ladder_host_ms_per_query", "device_wait_ms_per_query",
       "fetch_ms_per_query", "idle_in_device_wait_share"]


def ms(events):
    return [(n, a * MS, b * MS) for n, a, b in events]


# two requests on the caller's line; one unknown span name
CALLER = ms([
    (R, 10, 30), ("rest.search", 11, 29),
    ("indices:data/read/search", 12, 28), ("query_phase", 13, 24),
    ("search.plan", 13, 15), ("fastpath.frontier", 15, 17),
    ("fastpath.verify", 17, 22), ("device.wait", 18, 21),
    ("mystery.rung", 22, 23), ("fetch_phase", 25, 27),
    (R, 32, 50), ("rest.search", 33, 49),
    ("indices:data/read/search", 34, 48), ("query_phase", 35, 45),
    ("device.wait", 38, 42),
    ("rest.search", 52, 60)])       # after the window: the check's query
# busy 10..11 (clipped), 19..20, 39..41; the idle gap 20..39 crosses the
# request boundary and fourteen self segments
DEVICES = {"/device:TPU:0": {"modules": [], "ops": ms([
    ("fusion.1", 0, 11), ("%fused_bm25_topk_impact.1 = custom-call(", 19, 20),
    ("fusion.2", 39, 41)])}}


def test_self_times_partition_each_request():
    out = sr.reduce_events(DEVICES, [CALLER])
    assert out["requests"] == 2 and out["threads"] == 1
    assert out["window_s"] == pytest.approx(0.040)
    assert out["request_s"] == pytest.approx(0.038)
    assert out["program_s"] == pytest.approx(0.034)
    assert sum(r["self_s"] for r in out["spans"].values()) == \
        pytest.approx(out["request_s"], rel=1e-12)
    layers = out["layers"]
    assert layers.pop(sr.HARNESS) == pytest.approx(0.004)
    assert sum(layers.values()) == pytest.approx(out["program_s"], rel=1e-12)
    assert out["spans"]["rest.search"]["count"] == 2     # not the third
    assert out["spans"]["device.wait"] == {
        "count": 2, "total_s": pytest.approx(0.007),
        "self_s": pytest.approx(0.007)}
    assert out["spans"]["fastpath.verify"]["self_s"] == pytest.approx(0.002)
    assert layers == {
        "transport": pytest.approx(0.004),
        "coordinator": pytest.approx(0.003 + 0.004),
        # search.plan 2, query_phase 1 + 6, and the unknown span's 1
        "plan + jit cache": pytest.approx(0.010),
        "serving ladder": pytest.approx(0.004),
        "device": pytest.approx(0.007),
        "fetch": pytest.approx(0.002)}


def test_an_unknown_span_lands_in_its_enclosing_layer_and_is_listed():
    out = sr.reduce_events(DEVICES, [CALLER])
    assert out["unknown"] == ["mystery.rung"]
    assert sr.layer_of("mystery.rung") is None
    assert sr.layer_of("fastpath.quality_tier") == "serving ladder"
    assert sr.layer_of("impactpath.gather") == "serving ladder"
    assert out["idle"]["by_layer"]["plan + jit cache"] == \
        pytest.approx(0.002 + 0.001 + 0.001 + 0.003 + 0.003)


def test_idle_is_apportioned_across_spans_and_sums_to_trace_reduce():
    out = sr.reduce_events(DEVICES, [CALLER])
    requests = sorted((a, b) for n, a, b in CALLER if n == R)
    outside = tr.reduce_events(DEVICES, requests)
    idle = out["idle"]
    assert idle["in_requests_s"] + idle["between_requests_s"] == \
        pytest.approx(outside["window_s"] - outside["busy_s"], rel=1e-12)
    assert idle["between_requests_s"] == pytest.approx(0.002)
    # 18..19 and 20..21 of the first wait, 38..39 and 41..42 of the second
    assert idle["by_span"]["device.wait"] == pytest.approx(0.004)
    assert idle["by_span"]["mystery.rung"] == pytest.approx(0.001)
    assert idle["by_span"]["fetch_phase"] == pytest.approx(0.002)
    assert idle["by_span"][R] == pytest.approx(0.001 + 0.001 + 0.001)
    assert sum(idle["by_layer"].values()) == \
        pytest.approx(idle["in_requests_s"])
    # trace_reduce gives the whole 19 ms gap to where its middle lies
    assert dict(map(tuple, outside["breakdown"]["idle_gaps"]))[
        "inside a request, all gaps"] == pytest.approx(0.036)


def test_two_device_planes_are_averaged_like_trace_reduce():
    devices = dict(DEVICES, **{"/device:TPU:1": {"modules": [], "ops": ms(
        [("fusion.9", 12, 48)])}})
    out = sr.reduce_events(devices, [CALLER])
    outside = tr.reduce_events(devices, [(10 * MS, 30 * MS),
                                         (32 * MS, 50 * MS)])
    idle = out["idle"]
    assert idle["in_requests_s"] + idle["between_requests_s"] == \
        pytest.approx(outside["window_s"] - outside["busy_s"], rel=1e-12)
    assert idle["between_requests_s"] == pytest.approx(0.001)


def test_pool_thread_spans_are_counted_and_a_bare_node_trace_reads():
    worker = ms([("rest.search", 14, 16), ("rest.search", 70, 80)])
    out = sr.reduce_events(DEVICES, [CALLER, worker])
    assert out["threads"] == 2
    assert out["spans"]["rest.search"]["count"] == 3
    assert out["layers"]["transport"] == pytest.approx(0.006)
    # no `bench.request`: a node traced as it runs; its top-level spans
    # are the requests
    bare = [e for e in CALLER if e[0] != R]
    out = sr.reduce_events(DEVICES, [bare])
    assert out["requests"] == 3 and sr.HARNESS not in out["layers"]
    assert out["program_s"] == out["request_s"] == pytest.approx(0.042)
    assert sum(out["layers"].values()) == pytest.approx(0.042, rel=1e-12)


def test_nothing_to_read_is_none(tmp_path, monkeypatch):
    # the parent commit's program writes no span
    assert sr.reduce_events(DEVICES, [ms([(R, 10, 30), (R, 32, 50)])]) \
        is None
    monkeypatch.setattr(sr, "OUT_DIR", str(tmp_path))
    ctx = {"trace": {"requests": 2, "queries": 2}}
    assert sr.for_ctx(ctx) is None
    assert all(run.read_layer_metric(m, ctx) is None for m in NEW)


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*spans*.xplane.pb.gz")))


@pytest.mark.parametrize("packed", RECORDED or [None])
def test_recorded_trace_with_spans(packed, tmp_path, monkeypatch):
    if packed is None:
        pytest.skip("no recorded trace with spans under tests/data")
    path = str(tmp_path / "trace" / "recorded.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with gzip.open(packed) as src, open(path, "wb") as dst:
        dst.write(src.read())
    monkeypatch.setattr(sr, "OUT_DIR", str(tmp_path))
    outside = tr.reduce_file(path)
    ctx = {"trace": dict(outside, queries=outside["requests"])}
    values = {m: run.read_layer_metric(m, ctx) for m in NEW}
    assert all(isinstance(v, float) for v in values.values()), values
    out = sr.for_ctx(ctx)
    assert out["requests"] == 5 and out["unknown"] == []
    six = sum(values[m] for m in NEW[:6])
    rest = out["spans"]["rest.search"]
    assert six == pytest.approx(1e3 * rest["total_s"] / rest["count"],
                                rel=0.01)
    assert 0 <= values["idle_in_device_wait_share"] <= 100
    idle = out["idle"]
    assert idle["in_requests_s"] + idle["between_requests_s"] == \
        pytest.approx(outside["window_s"] - outside["busy_s"], rel=1e-6)
    # a wrong count of traced requests is another run's trace
    assert sr.for_ctx({"trace": {"requests": 6, "queries": 6}}) is None
    assert sr.tables(out).count("\n") > 20
