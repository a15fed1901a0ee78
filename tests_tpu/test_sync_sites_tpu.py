"""TPU-hardware check that the served path reads the device only where it says
so: the request shapes of the benchmark's six cells, each kind at a small
size, under `jax_transfer_guard_device_to_host = "disallow"`. An explicit
`jax.device_get` (every one of the program's sits inside a `device.wait`
span) passes the guard; an implicit read (`np.asarray`, `int`, `float`,
`bool` of a device array) is a device-to-host hop no span names, and the
guard refuses it. A CPU cannot see one: its arrays are host memory and the
guard never fires, so only this file holds PERF.md section 3's list of sync
sites to the program.

Every implicit read is caught where it happens (`ArrayImpl._value`, the one
getter all of them go through), written down with the program's innermost
frame, and then let through, so one pass lists every site of a path and not
only its first. Each kind warms its shapes with one pass of twins first, as
the benchmark does; what is held is the second pass, of other bodies.
On a real chip: `python -m pytest tests_tpu/test_sync_sites_tpu.py -q -s`."""

import copy
import gc
import os
import sys
import traceback

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import run as harness                          # noqa: E402

from opensearch_tpu.search import fastpath, impactpath    # noqa: E402
from opensearch_tpu.utils.trace import TRACER             # noqa: E402

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")

REQUESTS = 16       # two rotations of the eight operations of a dashboard


def _msmarco(config, traffic):
    # a tenth of the shard, and bands from the most frequent terms on, so
    # that rows longer than the head (the device rescore) still occur
    config["ndocs"] = 220_000
    traffic["params"].update(rank_lo=2, rank_hi=2000)


def _vectors(config, traffic):
    config["generator"] = dict(config["generator"], topics=int(
        config["generator"]["topics"]) * 65_536 // int(config["ndocs"]))
    config["ndocs"] = 65_536


def _events(config, traffic):
    config["ndocs"] = 400_000


def _trips(config, traffic):
    config["ndocs"] = 200_000


# cell -> how its configuration is cut; treccovid is small as it stands
CELLS = {"treccovid.search1.long": lambda config, traffic: None,
         "msmarco.search1.selective": _msmarco,
         "httplogs.search1.dashboard": _events,
         "nyctaxis.search1.analyst": _trips,
         "cohere10m.search1.knn100": _vectors,
         "big5.search1.terms": _events}


class ImplicitReads:
    """While active: every device-to-host read the guard refuses is noted
    (the program's innermost frame, the array's shape) and then made."""

    def __init__(self):
        from jax._src import array
        self.cls = array.ArrayImpl
        self.getter = self.cls.__dict__["_value"]
        self.found = []

    def _value(self, arr):
        try:
            return self.getter.fget(arr)
        except Exception as e:      # the guard's error type is jaxlib's own
            if "device-to-host" not in str(e).lower():
                raise
        frames = [f for f in traceback.extract_stack()
                  if os.sep + "opensearch_tpu" + os.sep in f.filename]
        at = frames[-1] if frames else traceback.extract_stack()[-3]
        self.found.append(
            f"{os.path.relpath(at.filename, ROOT)}:{at.lineno} "
            f"{at.name}: {arr.dtype}{list(arr.shape)}")
        with jax.transfer_guard_device_to_host("allow"):
            return self.getter.fget(arr)

    def __enter__(self):
        spy = self
        self.cls._value = property(lambda arr: spy._value(arr))
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        return self

    def __exit__(self, *exc):
        jax.config.update("jax_transfer_guard_device_to_host", "allow")
        self.cls._value = self.getter
        return False


def test_the_guard_is_live_and_the_spy_sees_what_it_refuses():
    x = jax.numpy.arange(4) + 1
    with ImplicitReads() as reads:
        assert jax.device_get(x).tolist() == [1, 2, 3, 4]   # explicit
        assert not reads.found
        y = jax.numpy.arange(3) * 2
        assert np.asarray(y).tolist() == [0, 2, 4]          # implicit
        assert int((jax.numpy.arange(3) + 5)[1]) == 6
    assert len(reads.found) == 2, reads.found
    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    try:
        with pytest.raises(Exception, match="(?i)device-to-host"):
            np.asarray(jax.numpy.arange(5) * 3)
    finally:
        jax.config.update("jax_transfer_guard_device_to_host", "allow")


def _walk(span):
    yield span
    for ch in span.children:
        yield from _walk(ch)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_request_shapes_read_the_device_only_in_device_wait(cell):
    from opensearch_tpu.rest.client import RestClient
    loaded = copy.deepcopy(harness.load_cell(cell))
    config, traffic = loaded["config"], loaded["traffic"]
    CELLS[cell](config, traffic)
    kind = harness.load_kind(config.get("deployment_kind",
                                        harness.DEFAULT_KIND))
    client = RestClient()
    built = kind.build(config, 2147483693, client, harness.INDEX)
    stream = kind.stream(built, traffic, int(traffic["pool_seed"]))
    pool = stream.take(REQUESTS)
    request = traffic["request"]
    for spec in pool:                               # the shapes, warmed
        harness.send(client, request, [stream.twin(spec)])
    ladder0 = dict(fastpath.STATS), dict(fastpath.RESCORE_STATS), \
        dict(impactpath.STATS)
    TRACER._traces.clear()
    with ImplicitReads() as reads:
        responses = [harness.send(client, request, [spec])[0]
                     for spec in pool]
    assert all("error" not in r and not r["_shards"]["failed"]
               for r in responses), responses
    roots = list(TRACER._traces)[-REQUESTS:]
    waits = [s.attributes for r in roots for s in _walk(r)
             if s.name == "device.wait"]
    dispatches = [s.attributes for r in roots for s in _walk(r)
                  if s.name == "device.dispatch"]
    served = fastpath.STATS["pure_served"] - ladder0[0]["pure_served"]
    rescued = (fastpath.RESCORE_STATS["device_launches"]
               - ladder0[1]["device_launches"])
    impact = impactpath.STATS["served"] - ladder0[2]["served"]
    print(f"\n{cell}: {config['ndocs']} rows, {REQUESTS} requests, "
          f"{len(dispatches)} device.dispatch "
          f"{sorted({a['program'] for a in dispatches})}, "
          f"{len(waits)} device.wait "
          f"{sorted({a['program'] for a in waits})}; kernel-served "
          f"{served}, device rescores {rescued}, impact-path served "
          f"{impact}; implicit device reads: {len(reads.found)}")
    for site in sorted(set(reads.found)):
        print(f"  implicit read x{reads.found.count(site)}: {site}")
    assert waits and dispatches
    assert {a["program"] for a in waits} <= {a["program"]
                                             for a in dispatches}
    if cell == "msmarco.search1.selective":
        assert served and rescued       # both rungs of the cell's path ran
    if cell == "treccovid.search1.long":
        assert impact
    if cell == "nyctaxis.search1.analyst":
        assert {"program": "mask"} in waits
    assert not reads.found, sorted(set(reads.found))
    del client, built, stream
    gc.collect()
