"""The reduction from a profiler trace to numbers: on hand-made events
(busy union, idle share, per-name sums, gap attribution) and on a small
trace recorded on one v5e (`data/*.xplane.pb`, `record_trace.py`)."""

import glob
import gzip
import os

import pytest

import trace_reduce as tr

MS = 1_000_000      # ns


def test_union_clip_and_attribution_on_hand_made_events():
    requests = [(10 * MS, 30 * MS), (40 * MS, 60 * MS)]     # window 10..60
    ops = [("fusion.1", 0, 12 * MS),            # starts before the window
           ("%fused_bm25_topk_impact.1 = (f32[1,128]{1,0}, s32[1,128]{1,0}) "
            "custom-call(s32[4,1]{1,0} %copy)", 15 * MS, 20 * MS),
           ("fusion.1", 18 * MS, 22 * MS),      # overlaps the kernel
           ("copy.3", 45 * MS, 50 * MS),
           ("copy.3", 70 * MS, 80 * MS)]        # after the window
    modules = [("jit_run(123)", 0, 12 * MS),
               ("jit_fused_bm25_topk_impact(77)", 15 * MS, 20 * MS),
               ("jit_run(123)", 18 * MS, 22 * MS),
               ("jit_run(9)", 45 * MS, 50 * MS)]
    out = tr.reduce_events({"/device:TPU:0": {"ops": ops,
                                              "modules": modules}}, requests)
    assert out["window_s"] == pytest.approx(0.050)
    assert out["requests"] == 2
    # busy: [10,12] + [15,22] + [45,50] = 14 ms
    assert out["busy_s"] == pytest.approx(0.014)
    assert out["kernel_s"] == pytest.approx(0.005)
    assert out["op_s"]["fusion.1"] == pytest.approx(0.006)
    assert out["op_s"]["copy.3"] == pytest.approx(0.005)
    gaps = dict((n, s) for n, s in out["breakdown"]["idle_gaps"]
                if n.endswith("all gaps"))
    # idle: 12..15, 22..45, 50..60 = 36 ms; 22..45 has its middle in no
    # request, the other two lie inside requests
    assert gaps["inside a request, all gaps"] == pytest.approx(0.013)
    assert gaps["between requests (generator), all gaps"] == \
        pytest.approx(0.023)
    assert out["module_s"]["jit_run"] == pytest.approx(0.011)
    assert out["breakdown"]["device_ops"][0] == [
        "program jit_run", pytest.approx(0.011)]
    assert ["op fusion.1", pytest.approx(0.006)] in \
        out["breakdown"]["device_ops"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_two_device_planes_are_averaged():
    requests = [(0, 10 * MS)]
    out = tr.reduce_events(
        {"/device:TPU:0": {"ops": [("a", 0, 4 * MS)], "modules": []},
         "/device:TPU:1": {"ops": [("a", 0, 2 * MS)], "modules": []}},
        requests)
    assert out["busy_s"] == pytest.approx(0.003)
    assert out["device_planes"] == 2


def test_names_are_shortened():
    hlo = ("%fusion.31 = s32[524288]{0:T(1024)S(1)} fusion(s32[4096]{0} "
           "%get-tuple-element.88), kind=kCustom, calls=%fused.clone")
    assert tr.short_op(hlo) == "%fusion.31 s32[524288] fusion"
    tup = ("%while.4 = (s32[]{:T(128)}, s32[524288]{0}) while((s32[]) "
           "%tuple.23), condition=%c, body=%b")
    assert tr.short_op(tup) == "%while.4 (s32[] while"
    assert tr.short_module("jit_run(17250395598174324247)") == "jit_run"
    assert tr.is_kernel("%fused_bm25_topk_tfdl.3 = (f32[32,128]{1,0}) "
                        "custom-call(s32[32,8]{1,0} %copy)")
    assert not tr.is_kernel("%custom-call.16 = s32[524288]{0} "
                            "custom-call(s32[524288]{0} %x)")
    assert not tr.is_kernel(hlo)


def test_a_trace_without_requests_or_devices_is_an_error():
    with pytest.raises(SystemExit, match="bench.request"):
        tr.reduce_events({"/device:TPU:0": {"ops": [], "modules": []}}, [])
    with pytest.raises(SystemExit, match="device plane"):
        tr.reduce_events({}, [(0, 1)])


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*.xplane.pb.gz")))


@pytest.mark.parametrize("packed", RECORDED or [None])
def test_recorded_trace(packed, tmp_path):
    if packed is None:
        pytest.skip("no recorded trace under tests/data")
    path = str(tmp_path / "recorded.xplane.pb")
    with gzip.open(packed) as src, open(path, "wb") as dst:
        dst.write(src.read())
    out = tr.reduce_file(path)
    assert out["requests"] == 5
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["kernel_s"] <= out["busy_s"]
    assert sum(out["op_s"].values()) >= out["busy_s"] * 0.5
    assert out["breakdown"]["device_ops"]
    idle = sum(s for n, s in out["breakdown"]["idle_gaps"]
               if n.endswith("all gaps"))
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
