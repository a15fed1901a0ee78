"""chip_smoke.py rehearsed without the chip: its phase bodies at 1-2,000
documents with the REAL kernels interpreted, its refusal to run on the
CPU, and the one rule that places the compilation cache. Everything is
steered from here (backend flag, interpret mode, head size, which queries
repeat singly) — the program itself has no option for any of it."""

import os
import subprocess
import sys

import pytest

import jax
from jax.experimental.pallas import tpu as pltpu

import chip_smoke
from opensearch_tpu.search import fastpath
from opensearch_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def as_on_chip(monkeypatch):
    """What only a TPU backend reaches, reached on the CPU: the fastpath
    on, its kernels interpreted, the device rescore, heads small enough
    that a 2,000-doc corpus climbs the pruned ladder. The 8 virtual CPU
    devices of conftest.py must not switch the mesh path on: the smoke's
    one-chip phases run a plain node."""
    monkeypatch.setenv("OPENSEARCH_TPU_MESH", "0")
    monkeypatch.setattr(fastpath, "_backend_ok", True)
    monkeypatch.setattr(fastpath, "L_HEAD", 64)
    fastpath.set_rescore_mode("device")
    try:
        with pltpu.force_tpu_interpret_mode():
            yield chip_smoke.CompileMeter()
    finally:
        fastpath.set_rescore_mode(None)


def test_phase_a_body(as_on_chip, tmp_path):
    assert not chip_smoke.kernels_lower_to_mosaic()    # interpreted here
    out = chip_smoke.phase_a(str(tmp_path / "data"), 0, 1000, as_on_chip)
    # every comparison inside held (it raises otherwise); the kernels,
    # not their fallback, answered
    assert out["counters"]["fastpath.pure_served"] > 0
    assert out["counters"]["fastpath.bool_served"] > 0
    assert not out["counters"]["fastpath.fallback"]
    assert out["cold"]["programs"] > 0


def test_phase_b_body(as_on_chip, monkeypatch):
    # one single (a stopword pair) instead of sixteen:
    # each new shape is one more program for the interpreter to lower,
    # and this file has a minute
    monkeypatch.setattr(chip_smoke, "SINGLES", [41])
    out = chip_smoke.phase_b(0, 2000, as_on_chip)
    c = out["counters"]
    assert c["fastpath.pure_served"] > 0 and c["fastpath.bool_served"] > 0
    assert not c["fastpath.fallback"]
    # the stopword-class queries: heads certify some, the rest are
    # rescued by the device rescore
    assert c["fastpath.pruned_served"] > 0
    assert c["fastpath.rescore.device_launches"] > 0
    assert out["warm"]["programs"] == 0         # nothing recompiles warm
    assert "numpy_dense" in out["reference"]
    # the CPU backend gives no memory statistics: on the chip `verdict`
    # turns exactly this read-out into a failure
    assert out["memory"]["ledger_vs_device"] is None
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        chip_smoke.verdict([out], chips=1)
    monkeypatch.setattr(chip_smoke, "kernels_lower_to_mosaic", lambda: True)
    with pytest.raises(chip_smoke.SmokeFailure, match="memory statistics"):
        chip_smoke.verdict([out], chips=1)


def test_phase_mesh_body(monkeypatch):
    """The `--chips 4` phase on conftest.py's virtual CPU devices: every
    shard planted with its own id range, every search dispatched to the
    mesh, every page held to the host shard loop's (on the chip the phase
    fails on one stopword-class query today, ROADMAP S0)."""
    monkeypatch.setattr(chip_smoke, "SINGLES", [41])
    out = chip_smoke.phase_mesh(0, 4000, chip_smoke.CompileMeter())
    assert out["shards"] == 4 and out["ndocs"] == 4000
    assert out["mesh_dispatched"] >= 3 and not out["mesh_declined"]
    assert out["warm_vs_cold"]["pages_not_bit_identical"] == 0


def test_hold_page_raises_what_the_benchmarks_rule_finds():
    ref = {"total": 5, "relation": "eq", "ids": ["3", "1", "2"],
           "scores": [2.0, 1.0, 1.0]}
    ok = dict(ref, ids=["3", "2", "1"])     # the tied pair may swap
    chip_smoke.hold_page("t", ok, ref)
    chip_smoke.hold_page("t", dict(ok, total=4, relation="gte"), ref)
    # a reference whose own total is a bound decides no total; one with
    # no page decides the total alone
    chip_smoke.hold_page("t", dict(ok, total=9), dict(ref, total=-1,
                                                      relation="gte"))
    chip_smoke.hold_page("t", ok, {"total": 5, "relation": "eq"})
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.hold_page("t", ok, {"total": 6, "relation": "eq"})
    for bad in (dict(ref, ids=["1", "3", "2"]),          # untied rank moved
                dict(ref, scores=[2.0001, 1.0, 1.0]),    # score off by 5e-5
                dict(ref, total=6),                      # eq total differs
                dict(ref, total=6, relation="gte"),      # bound above exact
                dict(ref, ids=["3", "1"], scores=[2.0, 1.0])):
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.hold_page("t", bad, ref)


def test_refuses_the_cpu_before_building_anything(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable,
                        os.path.join(_REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=str(tmp_path))
    assert r.returncode == 2
    assert r.stdout == ""       # no phase, no result under any metric name
    assert "needs a TPU" in r.stderr


@pytest.fixture()
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_placed_by_the_environment(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.place_compile_cache() == want      # fixed path


def test_verdict_needs_the_kernels_to_have_served_phase_b(monkeypatch):
    monkeypatch.setattr(chip_smoke, "kernels_lower_to_mosaic", lambda: True)
    mem = {"ledger_vs_device": {"ok": True},
           "per_device_bytes_in_use": [1]}
    served = {"fastpath.pure_served": 100, "fastpath.bool_served": 44}
    chip_smoke.verdict([{"phase": "B", "memory": mem, "counters": served}],
                       chips=1)
    for missing in served:
        some = {k: v for k, v in served.items() if k != missing}
        with pytest.raises(chip_smoke.SmokeFailure, match=missing):
            chip_smoke.verdict(
                [{"phase": "B", "memory": mem, "counters": some}], chips=1)
    with pytest.raises(chip_smoke.SmokeFailure, match="not spread"):
        chip_smoke.verdict([{"phase": "mesh", "counters": {}, "memory": dict(
            mem, per_device_bytes_in_use=[7, 0, 0, 0])}], chips=4)
