"""The per-layer metric `agg_run_counted_share` (PR 31): its reader returns
None from a program without the counters, and 100.0 on the toy `http_logs`
collection (20,000 events on the CPU), whose events are in arrival order:
every `hourly_agg` of the cell and of the check is counted as runs, none by
a scatter-add."""

import copy

import run

CELL = "httplogs.search1.dashboard"
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


def test_nothing_without_the_counters():
    for counters in ({}, {"executor.params_h2d_bytes": 4096},
                     {"executor.agg_bucket_launches": 0,
                      "executor.agg_run_counted": 0}):
        assert run.read_layer_metric(
            "agg_run_counted_share", {"window": {"counters": counters}}) is None


def test_the_toy_collection_reads_100(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)
    loaded = copy.deepcopy(run.load_cell(CELL))
    loaded["config"]["ndocs"] = 20_000
    t = loaded["traffic"]
    t["pool_requests"], t["check_sample"], t["check_fresh"] = 16, 16, 8
    kind = run.load_kind("http_logs")
    c0 = kind.counters(None)
    result = run.run_cell(loaded, 3000000017, 60, False, DEVICE,
                          run.CompileMeter(), str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    moved = run.delta(kind.counters(None), c0)
    assert moved["executor.agg_bucket_launches"] > 0
    assert moved["executor.agg_run_counted"] \
        == moved["executor.agg_bucket_launches"]
    assert run.read_layer_metric(
        "agg_run_counted_share", {"window": {"counters": moved}}) == 100.0
