"""The harness's phases rehearsed without the chip: 2,000 documents, the
REAL kernels interpreted, the look for a chip skipped. Everything is
steered from here (backend flag, interpret mode, head size, sizes), never
through an option of the harness or the program."""

import copy
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
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


# sha256 over what a run sends and holds, in order (`sent_and_held`), as the
# parent's `run.py` gave it at PR 26 (commit dd6daa9, before the deployment
# kinds): computed there first with this same function, pinned here. The
# requests are a count that repeats exactly; `bm25_match` is that path moved.
PINNED = {
    ("treccovid.search1.long", 11):
        "746b133b04249b324ef63ddc4f754ca0b4f1e5df1a26da9bbc029ca348b58dc1",
    ("treccovid.search1.long", 3000000012):
        "c80ea359e3066ff36e93ed0ddbe883beac5c63e4644ad466c8150839862673ea",
    ("msmarco.search1.selective", 11):
        "c8ca8056d56444d84907063fa730be085431c314bc7ed297449bd65415e0fea0",
    ("msmarco.search1.selective", 3000000012):
        "44cd4385bd478dceaa28bb5191b44a6854822e45bf469755654ef20e1a2fc63e",
}


def sent_and_held(loaded, seed, meter, tmp_path, monkeypatch) -> str:
    """One run's bodies in the order sent (warm-up twins, pool, fresh), the
    `status` / `price` columns it planted and the bodies its check held."""
    import corpus
    import reference
    log = []
    real_send, real_plant = run.send, corpus.plant_index
    real_hold = reference.hold

    def send(client, kind, specs):
        log.append([q["body"] for q in specs])
        return real_send(client, kind, specs)

    def plant(client, index, csr, vocab, dl, status, price, settings):
        log.append(["columns", hashlib.sha256(
            np.asarray(status).tobytes()
            + np.asarray(price).tobytes()).hexdigest()])
        return real_plant(client, index, csr, vocab, dl, status, price,
                          settings)

    def hold(pairs, *a, **kw):
        log.append(["held"] + [s["body"] for s, _r in pairs])
        return real_hold(pairs, *a, **kw)
    monkeypatch.setattr(run, "send", send)
    monkeypatch.setattr(corpus, "plant_index", plant)
    monkeypatch.setattr(reference, "hold", hold)
    run.run_cell(loaded, seed, 60, False, DEVICE, meter, str(tmp_path))
    assert len(log) == 12       # 3 twins, 3 of the pool, 4 fresh, 2 marks
    return hashlib.sha256(json.dumps(log).encode()).hexdigest()


@pytest.mark.parametrize("cell,seed", sorted(PINNED))
def test_a_run_sends_and_holds_what_the_parent_did(cell, seed, as_on_chip,
                                                   meter, tmp_path,
                                                   monkeypatch):
    assert sent_and_held(small(cell), seed, meter, tmp_path,
                         monkeypatch) == PINNED[cell, seed]


# ---------------------------------------------------------------------
# the seam: a deployment kind is a file found by name
# ---------------------------------------------------------------------

TOY_DIR = os.path.join(run.HERE, "tests", "data", "deployments")


def test_an_unknown_kind_names_the_file_looked_for():
    want = os.path.join(run.HERE, "deployments", "http_logs.py")
    with pytest.raises(SystemExit) as e:
        run.load_kind("http_logs")
    assert want in str(e.value)


@pytest.mark.parametrize("member", run.KIND_MEMBERS)
def test_a_kind_that_lacks_a_member_names_it_and_its_file(member, tmp_path,
                                                          monkeypatch):
    rest = [m for m in run.KIND_MEMBERS if m != member]
    path = tmp_path / "partial.py"
    path.write_text("".join(f"def {m}(*a):\n    pass\n" for m in rest))
    monkeypatch.setattr(run, "KIND_DIRS", [str(tmp_path)])
    with pytest.raises(SystemExit) as e:
        run.load_kind("partial")
    assert str(path) in str(e.value) and f"lacks {member}" in str(e.value)


def test_a_run_looks_for_kinds_in_deployments_alone():
    assert run.KIND_DIRS == [os.path.join(run.HERE, "deployments")]
    for cell in CELLS:      # `load_kind` exits on a kind it cannot take
        assert run.load_kind(run.load_cell(cell)["config"].get(
            "deployment_kind", run.DEFAULT_KIND))


def toy() -> dict:
    """A cell of the fixture kind `columns_toy`: 2,000 rows, the three
    request shapes dealt in turn, everything else as a committed cell."""
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return {"cell": {"name": "toy.search1.columns", "chips": 1},
            "config": {"name": "columns-toy", "ndocs": 2000,
                       "deployment_kind": "columns_toy",
                       "index_settings": {"number_of_shards": 1,
                                          "number_of_replicas": 0}},
            "traffic": {"request": "search", "batch": 1, "loop": "closed",
                        "clients": 1, "size": 10, "pool_seed": 7,
                        "pool_requests": 9, "check_sample": 9,
                        "check_fresh": 6},
            "end_to_end": spec["end_to_end"], "per_layer": [], "peaks": {}}


@pytest.fixture()
def toy_kind(monkeypatch):
    monkeypatch.setattr(run, "KIND_DIRS", run.KIND_DIRS + [TOY_DIR])
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)


def test_a_second_kind_runs_a_cells_phases(toy_kind, meter, tmp_path,
                                           capsys):
    """Set-up, the warm-up of twins, the window and the check of a kind with
    no `match` in it, through `run_cell` as it is."""
    sent, real = [], run.send

    def logged(client, kind, specs):
        sent.extend(json.dumps(q["body"], sort_keys=True) for q in specs)
        return real(client, kind, specs)
    run.send = logged
    try:
        result = run.run_cell(toy(), 3000000021, 60, False, DEVICE, meter,
                              str(tmp_path))
    finally:
        run.send = real
    assert result["correct"] is True and result["failed"] == 0
    assert {"qps", "p50_ms", "setup_s"} <= set(result["metrics"])
    assert list(result)[-1] == "compared"
    assert all(v == [0, 0] for v in result["compared"].values())
    assert len(sent) == len(set(sent)) == 9 + 9 + 6
    assert sum('"aggs"' in b for b in sent) == 8
    assert sum('"sort"' in b for b in sent) == 8
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    window = [x for x in lines if x.get("readout") == "window"][0]
    assert window["ended_by"] == "pool" and window["requests"] == 9
    assert window["rows"] == 2000
    assert window["counters"] == {"request_cache.hit_count": 0,
                                  "request_cache.miss_count": 9,
                                  "request_cache.entries": 9}
    assert window["compile"]["programs"] == 0
    check = [x for x in lines if x.get("readout") == "check"][0]
    assert check["from_the_window"] == 9 and check["fresh"] == 6


def test_a_second_kinds_broken_path_is_not_correct(toy_kind, meter, tmp_path,
                                                   monkeypatch):
    """One bucket's count moved by one where it is produced."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def one_too_many(self, *a, **kw):
        resp = real(self, *a, **kw)
        for agg in resp.get("aggregations", {}).values():
            agg["buckets"][0]["doc_count"] += 1
        return resp
    monkeypatch.setattr(RestClient, "search", one_too_many)
    result = run.run_cell(toy(), 8, 60, False, DEVICE, meter, str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["bucket_mismatches"][0] > 0
    assert result["compared"]["total_mismatches"] == [0, 0]
    assert result["compared"]["rank_mismatches"] == [0, 0]


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
