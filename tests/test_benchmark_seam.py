"""`BENCHMARK.json` and the files it names, held together in tier-1: the
benchmark's own suite (`benchmark/tests`) runs by hand, so a PR that breaks
the seam between the harness and what it finds by name would otherwise
show only on the chip."""

import glob
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import queries                  # noqa: E402
import run as harness           # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


def _kind_of(config: dict):
    return harness.load_kind(config.get("deployment_kind",
                                        harness.DEFAULT_KIND))


def test_benchmark_json_loads_and_names_its_parts():
    assert SPEC["command"] == ["python3", "benchmark/run.py"]
    assert SPEC["paths"] == ["benchmark"]
    assert len(CELLS) == len(set(CELLS)) >= 3
    assert {c["name"] for c in SPEC["configs"]} \
        == {w["config"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_a_configurations_kind_resolves_to_its_four_members(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    body = json.load(open(os.path.join(ROOT, entry["file"])))
    assert body["name"] == config and body["reduced"] == entry["reduced"]
    kind = _kind_of(body)       # exits where the file or a member is missing
    assert all(callable(getattr(kind, m)) for m in harness.KIND_MEMBERS)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_traffic_names_a_generator_that_resolves(cell):
    """`bm25_match` looks its query generators up in `queries.py`; a kind
    with generators of its own lists them under `GENERATORS`."""
    loaded = harness.load_cell(cell)
    kind, name = _kind_of(loaded["config"]), loaded["traffic"]["generator"]
    own = getattr(kind, "GENERATORS", None)
    if own is not None:
        assert name in own
    else:
        assert callable(queries.generator(name))
    assert isinstance(loaded["traffic"]["params"], dict)
    # what the cell reports: set-up, another end-to-end metric, a layer
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and loaded["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_a_per_layer_metric_has_its_reader(metric):
    path = os.path.join(harness.HERE, "layer_metrics", metric + ".py")
    assert os.path.isfile(path)
    assert callable(harness._load_module(path, "reader_" + metric).read)


# the readers of a launch's spans and of the device's named stages (PR 38;
# `impact_gather_ms_per_query` PR 39)
LAUNCH_READERS = ("dispatch_ms_per_query", "launch_latency_ms_per_query",
                  "readback_ms_per_query", "device_scoped_share",
                  "impact_accumulate_ms_per_query",
                  "impact_gather_ms_per_query",
                  "rescore_probe_ms_per_query", "executor_topk_ms_per_query")


def test_the_new_readers_read_nothing_from_a_program_without_their_source(
        tmp_path, monkeypatch):
    """A per-layer reader returns None where the program has no such
    counter, span, scope or module (the parent of the PR that added it)."""
    import span_reduce
    monkeypatch.setattr(span_reduce, "OUT_DIR", str(tmp_path))  # no trace
    ctx = {"window": {"queries": 10, "counters": {}},
           "trace": {"queries": 10, "requests": 10, "module_s": {}}}
    for name in ("params_h2d_mib_per_query",
                 "executor_program_ms_per_query") + LAUNCH_READERS:
        assert harness.read_layer_metric(name, ctx) is None
    ctx["window"]["counters"]["executor.params_h2d_bytes"] = 10 << 20
    ctx["trace"]["module_s"]["jit_executor_program"] = 0.5
    assert harness.read_layer_metric("params_h2d_mib_per_query", ctx) == 1.0
    assert harness.read_layer_metric("executor_program_ms_per_query",
                                     ctx) == 50.0


@pytest.mark.parametrize("keys,want", [(None, None), (0, 0.0),
                                       (3_072 * 88, 3.072)])
def test_topk_sorted_kelems_reads_the_counter_or_nothing(keys, want):
    """`executor.topk_keys_sorted` / queries / 1,000; a program without the
    counter (the parent of the PR that added it) reports nothing."""
    ctx = {"window": {"queries": 88, "counters": {}}}
    if keys is not None:
        ctx["window"]["counters"]["executor.topk_keys_sorted"] = keys
    assert harness.read_layer_metric("topk_sorted_kelems_per_query",
                                     ctx) == want


@pytest.mark.parametrize("counters,want", [
    ({}, None),                                   # the parent: no counters
    ({"executor.agg_bucket_launches": 0,
      "executor.agg_run_counted": 0}, None),      # no aggregation launched
    ({"executor.agg_bucket_launches": 11,
      "executor.agg_run_counted": 11}, 100.0),
    ({"executor.agg_bucket_launches": 8,
      "executor.agg_run_counted": 2}, 25.0),
    ({"executor.agg_bucket_launches": 8}, None),  # half a pair is no pair
])
def test_agg_run_counted_share_reads_the_counters_or_nothing(counters, want):
    """100 x `executor.agg_run_counted` / `executor.agg_bucket_launches`;
    a program without the counters, or a window without a bucket
    aggregation, reports nothing."""
    ctx = {"window": {"queries": 88, "counters": counters}}
    assert harness.read_layer_metric("agg_run_counted_share", ctx) == want


# the recorded 5-request traces of `benchmark/tests/data`: two of programs
# that wrote no `device.dispatch` and named no stage (PR 24 / PR 25), two
# recorded with them (PR 38, `launches/`)
_DATA = os.path.join(harness.HERE, "tests", "data")
_RECORDED = sorted(os.path.relpath(p, _DATA) for p in glob.glob(
    os.path.join(_DATA, "**", "*.xplane.pb.gz"), recursive=True))


@pytest.mark.parametrize("packed", _RECORDED)
def test_the_launch_readers_on_a_recorded_trace(packed, tmp_path,
                                                monkeypatch):
    """On a trace of a program without the spans every reader of PR 38
    reads nothing; on one with them the three parts of a wait's region add
    up to the device's idle time there, within 2%."""
    import launch_reduce
    import span_reduce
    import trace_reduce
    path = str(tmp_path / "trace" / "recorded.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with gzip.open(os.path.join(_DATA, packed)) as src, \
            open(path, "wb") as dst:
        dst.write(src.read())
    monkeypatch.setattr(span_reduce, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(launch_reduce, "_memo", {})
    reduced = trace_reduce.reduce_file(path)
    ctx = {"trace": dict(reduced, queries=reduced["requests"])}
    values = {m: harness.read_layer_metric(m, ctx) for m in LAUNCH_READERS}
    if not packed.startswith("launches"):
        assert values == dict.fromkeys(LAUNCH_READERS)
        return
    seam = launch_reduce.seam_for_ctx(ctx)
    assert seam["dispatches"] == seam["modules"] == seam["launches"] > 0
    assert seam["identity_error"] < 0.02
    assert all(values[m] > 0 for m in LAUNCH_READERS[:3])
    assert 99 < values["device_scoped_share"] <= 100
    staged = {m: v for m, v in values.items()
              if m in LAUNCH_READERS[4:] and v is not None}
    assert set(staged) == ({"executor_topk_ms_per_query"}
                           if "httplogs" in packed
                           else {"rescore_probe_ms_per_query"})
    assert 0 < sum(staged.values()) <= 1e3 * reduced["busy_s"] / 5
    assert span_reduce.for_ctx(ctx)["unknown"] == ["device.dispatch"]
