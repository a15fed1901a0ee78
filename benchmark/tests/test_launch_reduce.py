"""The reduction of a launch, end to end (`launch_reduce.py`): on hand-made
events (a launch queued behind a running program, two programs in one
wait, a wait that reads what was read, an eager op no span launched, a host
that comes late to a finished device; the identity that holds the parts to
the device's idle time), on a hand-made `.xplane.pb` (a stage is a path
component, never a substring; a `while` takes its body's stage and the
body counts once), and on
the recorded traces: the two of programs without the spans reduce to
nothing in every new reader, the one recorded with them
(`data/launches/*.xplane.pb.gz`, `record_trace.py`) to numbers that add up."""

import glob
import gzip
import os

import pytest

import launch_reduce as lr
import run
import span_reduce as sr
import trace_reduce as tr

MS = 1_000_000      # ns
R = tr.REQUEST
D, W = lr.DISPATCH, lr.WAIT
SEAM = ["dispatch_ms_per_query", "launch_latency_ms_per_query",
        "readback_ms_per_query"]
STAGED = ["device_scoped_share", "impact_accumulate_ms_per_query",
          "rescore_probe_ms_per_query", "executor_topk_ms_per_query"]
DATA = os.path.join(os.path.dirname(__file__), "data")


def ms(events):
    return [(n, int(a * MS), int(b * MS)) for n, a, b in events]


def device(modules):
    """One plane whose ops fill its module events exactly."""
    return {"/device:TPU:0": {"modules": ms(modules), "ops": ms(modules)}}


def seam_ms(devices, caller):
    out = lr.seam(devices, [ms(caller)])
    return {k: round(v * 1e3, 6) if k.endswith("_s") else v
            for k, v in out.items()}


# request 1: B is dispatched while A is still queued and runs behind it
QUEUED = [(R, 10, 40), ("rest.search", 11, 39), (D, 12, 13), (D, 13.5, 14.5),
          (W, 15, 22)]
QUEUED_DEV = [("jit_a(1)", 14, 18), ("jit_b(2)", 18, 20)]


def test_a_launch_behind_a_running_program_counts_no_latency():
    out = seam_ms(device(QUEUED_DEV), QUEUED)
    assert out["dispatches"] == out["modules"] == out["launches"] == 2
    assert out["dispatch_s"] == 2.0
    assert out["launch_latency_s"] == 2.0      # A's 12..14 alone
    assert out["gap_s"] == 0.0
    assert out["readback_s"] == 2.0            # 20..22
    assert out["idle_s"] == 4.0 and out["identity_error"] == 0.0
    assert out["unmatched_modules"] == [] and out["unread_launches"] == 0


# request 2: two programs in one wait with the host between them; a wait
# that reads what the first read; an eager op; a host late to the wait
TWO = [(R, 50, 90), ("rest.search", 51, 89),
       (D, 52, 53), (D, 58, 59), (W, 59.5, 66),
       (W, 70, 71),
       (W, 74, 78),
       (D, 80, 81), (W, 85, 86)]
TWO_DEV = [("jit_c(3)", 54, 56), ("jit_d(4)", 60, 63),
           ("jit_convert_element_type(9)", 75, 76),
           ("jit_f(5)", 81.5, 82.5)]


def test_two_programs_in_one_wait_and_the_other_kinds_of_wait():
    out = seam_ms(device(TWO_DEV), TWO)
    assert (out["dispatches"], out["modules"], out["launches"],
            out["waits"]) == (3, 4, 3, 4)
    assert out["unmatched_modules"] == ["jit_convert_element_type"]
    # C 52..54, D 58..60, F 80..81.5
    assert out["launch_latency_s"] == 2.0 + 2.0 + 1.5
    # C's end to D's dispatch 56..58; before the eager op 74..75; F's end
    # to its wait 82.5..85
    assert out["gap_s"] == 2.0 + 1.0 + 2.5
    # 63..66, the whole second wait, 76..78, 85..86
    assert out["readback_s"] == 3.0 + 1.0 + 2.0 + 1.0
    assert out["idle_s"] == 9.0 + 1.0 + 3.0 + 5.0
    assert out["identity_error"] == 0.0


def test_both_requests_and_what_lies_outside_them():
    after = [(D, 95, 96), (W, 96, 99)]         # the check's query: outside
    devices = device(QUEUED_DEV + TWO_DEV + [("jit_g(7)", 96.5, 97)])
    out = seam_ms(devices, QUEUED + TWO + after)
    assert out["requests"] == 2 and out["dispatches"] == 5
    assert out["launch_latency_s"] == 7.5 and out["readback_s"] == 9.0
    assert out["launch_latency_s"] + out["gap_s"] + out["readback_s"] \
        == out["idle_s"] == 22.0
    # the identity is against the ops: a program whose ops leave 1 of its
    # 4 ms idle shows as that much of the 23
    devices["/device:TPU:0"]["ops"] = ms(
        [("%fusion.1", 14, 17)] + QUEUED_DEV[1:] + TWO_DEV)
    out = lr.seam(devices, [ms(QUEUED + TWO)])
    assert out["idle_s"] == pytest.approx(0.023)
    assert out["identity_error"] == pytest.approx(1 / 23)


def test_a_launch_no_wait_reads_is_counted_and_a_bare_trace_reads():
    caller = [(R, 10, 30), (D, 12, 13), (R, 32, 50), (D, 33, 34),
              (W, 35, 40)]
    out = seam_ms(device([("jit_a(1)", 14, 15), ("jit_b(2)", 36, 38)]),
                  caller)
    assert out["unread_launches"] == 1 and out["launches"] == 2
    assert out["launch_latency_s"] == 3.0      # the second request's alone
    # no `bench.request`: every top-level span is a request
    bare = [("rest.search", 10, 40)] + QUEUED[2:]
    assert seam_ms(device(QUEUED_DEV), bare)["launch_latency_s"] == 2.0


def test_nothing_to_read_is_none(tmp_path, monkeypatch):
    # the parent's program writes no `device.dispatch`
    old = [(R, 10, 40), ("rest.search", 11, 39), (W, 15, 22)]
    assert lr.seam(device(QUEUED_DEV), [ms(old)]) is None
    assert lr.seam({}, [ms(QUEUED)]) is None
    monkeypatch.setattr(sr, "OUT_DIR", str(tmp_path))
    ctx = {"trace": {"requests": 2, "queries": 2}}
    assert lr.seam_for_ctx(ctx) is None and lr.stages_for_ctx(ctx) is None
    assert all(run.read_layer_metric(m, ctx) is None
               for m in SEAM + STAGED)


# ---------------------------------------------------------------------
# stages: a hand-made xplane
# ---------------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def xplane(ops: list, named: bool = False) -> bytes:
    """One device plane: ops as (HLO line, provenance, offset ps,
    duration ps) on its `XLA Ops` line; `named`: the plane names its stats
    (7 is `tf_op`, 9 `source`) and every op carries a `source` too."""
    meta, events = b"", b""
    for i, (name, prov, off, dur) in enumerate(ops, 1):
        stat = _field(5, _field(1, 7) + _field(5, prov.encode())) if prov \
            else b""
        if named:
            stat += _field(5, _field(1, 9) + _field(5, b"/repo/aggs.dense"))
        body = _field(1, i) + _field(2, name.encode()) + stat
        meta += _field(4, _field(1, i) + _field(2, body))
        events += _field(4, _field(1, i) + _field(2, off) + _field(3, dur))
    stats = b"".join(_field(5, _field(1, sid) + _field(
        2, _field(1, sid) + _field(2, name)))
        for sid, name in ((7, b"tf_op"), (9, b"source"))) if named else b""
    line = _field(3, _field(2, b"XLA Ops") + events)
    host = _field(1, _field(2, b"/host:CPU"))
    return host + _field(1, _field(2, b"/device:TPU:0") + meta + stats
                         + line)


def test_stages_of_takes_components_not_substrings():
    assert lr.stages_of("jit(executor_program)/jit(main)/reduce_sum") == []
    assert lr.stages_of("jit(impact_program)/impact.accumulate/scatter-add") \
        == ["impact.accumulate"]
    assert lr.stages_of("jit(executor_program)/executor.match/knn.gather/"
                        "gather") == ["executor.match", "knn.gather"]
    assert lr.stages_of("jit(f)/vmap(executor.topk)/reshape") \
        == ["executor.topk"]
    assert lr.stages_of("jit(f)/executor.aggs/aggs.bucketed_sub/aggs.dense/"
                        "while/body/add") \
        == ["executor.aggs", "aggs.bucketed_sub", "aggs.dense"]
    assert lr.stages_of("jit(f)/my_executor.match/x") == []


OPS = [
    # a `while` has no provenance; its body's op is an event inside it
    ("%while.4 = s32[8] while(%t)", "", 0, 10),
    ("%fusion.3 = s32[8] fusion(%p)",
     "jit(rescore)/rescore.probe/while/body/gather", 2, 3),
    ("%fusion.9 = f32[8] fusion(%p)", "jit(rescore)/rescore.score/add",
     10, 5),
    ("%copy.1 = f32[8] copy(%p)", "jit(executor_program)/copy", 15, 5)]


@pytest.mark.parametrize("named", [False, True])
def test_stages_from_a_hand_made_xplane(tmp_path, named):
    path = tmp_path / "t.xplane.pb"
    ps = 1_000_000_000          # 1 ms
    path.write_bytes(xplane([(n, p, a * ps, d * ps) for n, p, a, d in OPS],
                            named))
    out = lr.stages(str(path))
    # the `while` takes its body's stage, and the body counts once; the
    # `source` stat names no stage, whatever its path looks like
    assert out["stage_s"] == {"rescore.probe": pytest.approx(0.010),
                              "rescore.score": pytest.approx(0.005)}
    assert out["scoped_s"] == pytest.approx(0.015)
    assert out["all_s"] == pytest.approx(0.020)         # the busy time
    assert out["ops"][0] == ("%while.4 s32[8] while", pytest.approx(0.010),
                             ["rescore.probe"])
    assert {n: found for n, _s, found in out["ops"]}[
        "%copy.1 f32[8] copy"] == []                    # no stage
    # a `while` over two stages takes neither
    path.write_bytes(xplane([
        ("%while.1 = s32[8] while(%t)", "", 0, 10 * ps),
        ("%a = s32[8] fusion(%p)", "jit(f)/impact.gather/x", 0, 4 * ps),
        ("%b = s32[8] fusion(%p)", "jit(f)/impact.accumulate/y", 5 * ps,
         4 * ps)], named))
    out = lr.stages(str(path))
    assert out["scoped_s"] == pytest.approx(0.008)
    assert out["all_s"] == pytest.approx(0.010)
    # a plane whose ops name no stage, and a file with no device plane
    path.write_bytes(xplane([("%copy.1 = f32[8] copy(%p)",
                              "jit(executor_program)/copy", 0, ps)], named))
    assert lr.stages(str(path)) is None
    path.write_bytes(_field(1, _field(2, b"/host:CPU")))
    assert lr.stages(str(path)) is None


# ---------------------------------------------------------------------
# recorded traces
# ---------------------------------------------------------------------

def _unpacked(packed, tmp_path, monkeypatch):
    path = str(tmp_path / "trace" / "recorded.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with gzip.open(packed) as src, open(path, "wb") as dst:
        dst.write(src.read())
    monkeypatch.setattr(sr, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(lr, "_memo", {})
    outside = tr.reduce_file(path)
    return path, {"trace": dict(outside, queries=outside["requests"])}


# the new ones lie a directory down: `test_trace_reduce.py` holds every
# trace beside the old ones to a Pallas kernel's time, and a log-analytics
# trace has none
OLD = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))
NEW = sorted(glob.glob(os.path.join(DATA, "launches", "*.xplane.pb.gz")))


@pytest.mark.parametrize("packed", OLD, ids=os.path.basename)
def test_a_trace_of_a_program_without_the_spans_reads_nothing(
        packed, tmp_path, monkeypatch):
    path, ctx = _unpacked(packed, tmp_path, monkeypatch)
    assert lr.seam_file(path) is None and lr.stages(path) is None
    assert {m: run.read_layer_metric(m, ctx) for m in SEAM + STAGED} \
        == dict.fromkeys(SEAM + STAGED)


@pytest.mark.parametrize("packed", NEW or [None],
                         ids=lambda p: os.path.basename(p or "none"))
def test_recorded_trace_with_launches(packed, tmp_path, monkeypatch):
    if packed is None:
        pytest.skip("no recorded trace with launches under tests/data")
    path, ctx = _unpacked(packed, tmp_path, monkeypatch)
    out = lr.seam_for_ctx(ctx)
    assert out["requests"] == 5
    # a launch a module event, each after its dispatch began
    assert out["dispatches"] == out["modules"] == out["launches"] > 0
    assert out["unmatched_modules"] == [] and out["unread_launches"] == 0
    assert out["identity_error"] < 0.02
    values = {m: run.read_layer_metric(m, ctx) for m in SEAM}
    assert all(isinstance(v, float) and v > 0 for v in values.values())
    spans = sr.for_ctx(ctx)
    assert spans["unknown"] == [lr.DISPATCH]
    # dispatch is a part of its callers' layers, and the parts of a wait's
    # region lie inside it
    assert values["dispatch_ms_per_query"] == pytest.approx(
        1e3 * spans["spans"][lr.DISPATCH]["total_s"] / 5)
    assert values["readback_ms_per_query"] <= \
        1e3 * spans["spans"][lr.WAIT]["total_s"] / 5
    st = lr.stages_for_ctx(ctx)
    share = run.read_layer_metric("device_scoped_share", ctx)
    assert 0 < share <= 100 and st["scoped_s"] <= st["all_s"]
    staged = {m: run.read_layer_metric(m, ctx) for m in STAGED[1:]}
    assert any(v is not None for v in staged.values()), staged
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / 5
    assert all(v is None or 0 < v <= busy_ms for v in staged.values())
    # a wrong count of traced requests is another run's trace
    assert lr.seam_for_ctx({"trace": {"requests": 6, "queries": 6}}) is None
    assert lr.tables(path).count("\n") > 20
