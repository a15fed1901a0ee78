"""The harness's phases rehearsed without the chip: 2,000 documents, the
REAL kernels interpreted, the look for a chip skipped. Everything is
steered from here (backend flag, interpret mode, head size, sizes), never
through an option of the harness or the program."""

import copy
import json
import os
import subprocess
import sys

import pytest

import run

ROOT = run.ROOT
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


@pytest.fixture(scope="module")
def meter():
    return run.CompileMeter()


@pytest.fixture()
def as_on_chip(monkeypatch):
    """What only a TPU backend reaches, reached on the CPU: the fastpath
    on, its kernels interpreted, the device rescore, heads small enough
    that 2,000 documents climb the pruned ladder."""
    from jax.experimental.pallas import tpu as pltpu
    from opensearch_tpu.search import fastpath
    monkeypatch.setattr(fastpath, "_backend_ok", True)
    monkeypatch.setattr(fastpath, "L_HEAD", 64)
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)
    fastpath.set_rescore_mode("device")
    try:
        with pltpu.force_tpu_interpret_mode():
            yield
    finally:
        fastpath.set_rescore_mode(None)


# the traffic mix kept for the blocked cell (PERF.md section 7), rehearsed
# so that it stays runnable as data
SPARE = [("msmarco.search1.selective", "msearch32.natural")]


def small(cell: str, traffic: str = None) -> dict:
    """The cell as committed (or with another traffic file), cut to a size
    a test can hold."""
    loaded = copy.deepcopy(run.load_cell(cell))
    if traffic:
        loaded["traffic"] = json.load(open(os.path.join(
            run.HERE, "traffic", traffic + ".json")))
    loaded["config"]["ndocs"] = 2000
    loaded["config"]["generator"]["vocab"] = 3000
    t = loaded["traffic"]
    t["batch"] = min(t["batch"], 4)
    t["pool_requests"], t["check_sample"] = 3, 8
    t["check_fresh"] = min(t["check_fresh"], 4)
    t["trace"] = {"min_requests": 1, "min_seconds": 0}
    if t["generator"] == "df_rank_band":
        t["params"].update(rank_lo=20, rank_hi=1500)
    return loaded


@pytest.mark.parametrize("cell,traffic", [(c, None) for c in CELLS] + SPARE)
def test_cell_phases_end_to_end(cell, traffic, as_on_chip, meter, tmp_path,
                                capsys):
    loaded = small(cell, traffic)
    result = run.run_cell(loaded, 5, 0.5, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in loaded["end_to_end"]} - {"p95_ms"}
    assert want <= set(result["metrics"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    check = [x for x in lines if x.get("readout") == "check"][0]
    assert check["compared"] > 0
    for value, limit in check["numbers"].values():
        assert value <= limit
    window = [x for x in lines if x.get("readout") == "window"][0]
    assert window["counters"]["request_cache.hit_count"] == 0
    assert check["from_the_window"] > 0 and check["fresh"] > 0
    # the warm-up pass over the window's own pool compiled every shape
    assert window["requests"] <= window["pool_requests"] == 3
    assert window["compile"]["programs"] == 0


@pytest.mark.parametrize("corpus_seed", [7, 2147483693, 3000000021])
@pytest.mark.parametrize("cell", CELLS)
def test_other_collections_hold_the_rule(cell, corpus_seed, as_on_chip,
                                         meter, tmp_path):
    """The timed cells serve one fixed collection (the program's compile
    keys follow its planes' exact lengths); here the same phases hold the
    rule on collections no chip run sees."""
    loaded = small(cell)
    loaded["config"]["corpus_seed"] = corpus_seed
    result = run.run_cell(loaded, corpus_seed + 1, 0.5, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0


def test_another_loop_or_client_count_is_refused():
    traffic = dict(small(CELLS[0])["traffic"], clients=8)
    with pytest.raises(SystemExit, match="only the closed loop"):
        run.Window(None, traffic, [], 1.0)


def test_every_seed_sends_the_pool_in_another_order(as_on_chip, meter,
                                                    tmp_path, capsys):
    """The seed may not change the work: two seeds answer the same pool of
    requests, in another order, and check other fresh queries."""
    sent = {}
    for seed in (11, 3000000012):
        loaded = small("msmarco.search1.selective")
        loaded["traffic"]["pool_requests"] = 12
        real, log = run.send, []

        def logged(client, kind, specs, _real=real, _log=log):
            _log.append(json.dumps([q["body"] for q in specs]))
            return _real(client, kind, specs)
        run.send = logged
        try:
            run.run_cell(loaded, seed, 60, False, DEVICE, meter,
                         str(tmp_path))
        finally:
            run.send = real
        lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
        window = [x for x in lines if x.get("readout") == "window"][0]
        assert window["ended_by"] == "pool" and window["requests"] == 12
        sent[seed] = log[12:24], log[24:]   # after the 12 twins
    (w1, f1), (w2, f2) = sent.values()
    assert sorted(w1) == sorted(w2) and w1 != w2
    assert len(f1) == len(f2) > 0 and not set(f1) & set(f2)
    assert not set(f1) & set(w1)


def test_a_broken_timed_path_is_not_correct(as_on_chip, meter, tmp_path,
                                            monkeypatch):
    """An answer altered where it is produced: the tenth hit's score of
    every response moved by 1e-4 relative. `correct` comes out false."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def off_by_a_little(self, *a, **kw):
        resp = real(self, *a, **kw)
        for h in resp["hits"]["hits"][-2:]:
            h["_score"] *= 1.0 + 1e-4
        return resp
    monkeypatch.setattr(RestClient, "search", off_by_a_little)
    result = run.run_cell(small("treccovid.search1.long"), 6, 0.5, False,
                          DEVICE, meter, str(tmp_path))
    assert result["correct"] is False


def test_a_dropped_hit_is_not_correct(as_on_chip, meter, tmp_path,
                                      monkeypatch):
    """The kernel's best document left out of every msearch response."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.msearch

    def first_hit_lost(self, *a, **kw):
        out = real(self, *a, **kw)
        for resp in out["responses"]:
            del resp["hits"]["hits"][0]
        return out
    monkeypatch.setattr(RestClient, "msearch", first_hit_lost)
    result = run.run_cell(small(*SPARE[0]), 7, 0.5, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False


def test_run_py_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "3000000011", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert r.returncode != 0
    assert r.stdout == ""               # no result, no read-out
    assert "needs a TPU" in r.stderr
