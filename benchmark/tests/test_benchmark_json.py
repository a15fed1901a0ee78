"""`BENCHMARK.json` held to the contract's rules that need no chip: the
keys, the character rules of names and units, the lengths, and that every
name finds its file."""

import json
import os
import re

import run

SPEC_PATH = os.path.join(run.ROOT, "BENCHMARK.json")
SPEC = json.load(open(SPEC_PATH))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(SPEC_PATH) <= 64 * 1024
    assert SPEC["paths"] == ["benchmark"]
    assert len(SPEC["command"]) <= 32 and all(map(_line, SPEC["command"]))
    assert not any(w.startswith("/") or ".." in w for w in SPEC["command"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_configs_and_cells():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    assert len(cfgs) == len(SPEC["configs"]) <= 24
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert PATH.match(c["file"]) and c["file"].startswith("benchmark/")
        body = json.load(open(os.path.join(run.ROOT, c["file"])))
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) and k in body for k in c["reduced"])
        assert {"source", "assumed", "guarantees", "generator",
                "corpus_seed", "ndocs"} <= set(body)
        # the kind of deployment: optional, a name, and it finds its file
        # with the four members (`load_kind` exits otherwise)
        kind = body.get("deployment_kind", run.DEFAULT_KIND)
        assert NAME.match(kind) and run.load_kind(kind)
    assert len({c["file"] for c in SPEC["configs"]}) == len(cfgs)
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4) and _line(w["why"])
        assert os.path.isfile(os.path.join(run.HERE, "traffic",
                                           w["traffic"] + ".json"))
    assert {w["config"] for w in cells} == set(cfgs)    # each has a cell
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = SPEC["per_layer"]
    assert 1 <= len(layers) <= 128
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(run.HERE, "layer_metrics",
                                           m["name"] + ".py"))
    every = SPEC["end_to_end"] + layers
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells  # each is a cell
        assert len(set(m.get("workloads", []))) == len(m.get("workloads", []))
    for cell in cells:      # every cell reports setup_s, another, a layer
        mine = [m["name"] for m in SPEC["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert run.load_cell(cell)["per_layer"]


def test_the_ladders_metrics_are_listed_by_cell():
    """Metrics of a mechanism only `bm25_match` cells enter name their
    cells, so a cell of another kind does not report their zeros."""
    ladder = {"kernel_served_share", "ladder_escalated_share",
              "rescore_wall_ms_per_query", "rescore_probe_kelems_per_query",
              "kernel_ms_per_query"}
    bm25 = {w["name"] for w in SPEC["workloads"]
            if run.load_cell(w["name"])["config"].get(
                "deployment_kind", run.DEFAULT_KIND) == "bm25_match"}
    for m in SPEC["per_layer"]:
        if m["name"] in ladder:
            assert set(m["workloads"]) <= bm25


def test_peaks_name_their_source_and_an_unknown_kind_is_missing():
    peaks = json.load(open(os.path.join(run.HERE, "peaks.json")))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all("source" in row for row in peaks.values())
    assert peaks.get("TPU v9 imaginary") is None    # run_cell exits on it
