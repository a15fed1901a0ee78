#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `BENCHMARK.json`'s `workloads`; its configuration is
`benchmark/configs/<config>.json`, its traffic `benchmark/traffic/<traffic>.json`,
each per-layer metric `benchmark/layer_metrics/<metric>.py`, and what differs
between kinds of deployment (the data and its index, the request stream, the
reference with its rule, the program's counters)
`benchmark/deployments/<deployment_kind>.py`, all found by name: a later PR
adds cells, configurations, traffic mixes, metrics and kinds as new files
and new entries (README.md).

One process, which holds the chip itself. It exits non-zero with no result
unless JAX reports a TPU with exactly the cell's `chips` devices. Then:
set-up (the kind's `build`: the configuration's data made on the host,
indexed under an index the client created, promoted to HBM; then one
warm-up pass over the window's own pool of requests, every query as its
twin), the measured window (one caller in a closed loop, through
`RestClient.search` / `RestClient.msearch` in this process, until
`--seconds` are up or the pool is sent), and after the window the check: a
seeded sample of the answered queries, and fresh queries drawn from
`--seed`, held to the kind's plain reference by the kind's rule.
Read-outs go to stdout as one JSON object a line; the LAST line is the
result the contract fixes: with `--trace 0` the cell's end-to-end metrics,
with `--trace 1` its per-layer metrics, `device.busy_s` / `window_s` and a
`breakdown` from the profiler's trace of the window's first slice; either
way `compared` comes last: every number the check compared, beside its
limit (the same go to stderr as the run's last lines)."""

from __future__ import annotations

import time

T_START = time.time()          # process start, as near as Python sees it

import argparse                 # noqa: E402
import gc                       # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402

import numpy as np              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

INDEX = "bench"
DEFAULT_KIND = "bm25_match"     # a configuration without `deployment_kind`
KIND_MEMBERS = ("build", "stream", "hold", "counters")
# where `load_kind` looks; the tests add the directory of their fixture kind
KIND_DIRS = [os.path.join(HERE, "deployments")]


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(msg: str) -> None:
    print(f"[benchmark {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _load_json(*parts) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: {path} is missing")
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, alias: str):
    spec = importlib.util.spec_from_file_location(alias, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(name: str):
    """The module `<name>.py` of the first of `KIND_DIRS` that has it: a
    kind of deployment (README.md, "a deployment kind")."""
    paths = [os.path.join(d, name + ".py") for d in KIND_DIRS]
    path = next((p for p in paths if os.path.isfile(p)), None)
    if path is None:
        raise SystemExit(f"benchmark: no deployment kind {name!r} "
                         f"(looked for {', '.join(paths)})")
    mod = _load_module(path, f"deployment_kind_{name}")
    lacks = [m for m in KIND_MEMBERS if not callable(getattr(mod, m, None))]
    if lacks:
        raise SystemExit(f"benchmark: deployment kind {name!r} at {path} "
                         f"lacks {', '.join(lacks)} (a kind exposes "
                         f"{', '.join(KIND_MEMBERS)})")
    return mod


def load_cell(name: str) -> dict:
    """The cell `name` with its configuration, its traffic and the metrics
    it reports, as `BENCHMARK.json` and the files it names give them."""
    spec = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def mine(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layers = [m for m in spec["per_layer"]
              if mine(m) and m["moves"] in reported]
    return {"cell": cell,
            "config": _load_json(ROOT, cfg_entry["file"]),
            "traffic": _load_json(HERE, "traffic", cell["traffic"] + ".json"),
            "end_to_end": e2e, "per_layer": layers,
            "peaks": _load_json(HERE, "peaks.json")}


def require_device(chips: int) -> dict:
    """The device as JAX reports it; exit 2 unless it is a TPU with exactly
    `chips` devices, before anything is built. No path falls back."""
    import jax
    ds = jax.devices()
    dev = {"platform": ds[0].platform, "kind": ds[0].device_kind,
           "count": len(ds)}
    if dev["platform"] != "tpu" or dev["count"] != chips:
        print(f"benchmark: this cell needs a TPU with {chips} device(s); "
              f"JAX reports {dev}", file=sys.stderr)
        raise SystemExit(2)
    return dev


class CompileMeter:
    """Programs compiled or read from the persistent cache (either way the
    host traced and lowered them), and the seconds of trace, lowering and
    backend compile, from JAX's own monitoring events: every program of
    the process, the Pallas kernels included. (The pattern of
    `chip_smoke.CompileMeter`, copied.)"""

    _SECONDS = {"/jax/core/compile/backend_compile_duration": "backend_s",
                "/jax/core/compile/jaxpr_trace_duration": "trace_s",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s"}
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.v = {"programs": 0, "cache_hits": 0, "backend_s": 0.0,
                  "trace_s": 0.0, "lower_s": 0.0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        key = self._SECONDS.get(event)
        if key:
            self.v[key] += secs
            if key == "backend_s":
                self.v["programs"] += 1

    def _event(self, event, **_kw):
        if event == self._HIT:
            self.v["cache_hits"] += 1

    def mark(self) -> dict:
        return dict(self.v)

    def since(self, mark: dict) -> dict:
        return {k: self.v[k] - mark[k] for k in self.v}


def counters(deployment, client) -> dict:
    """The program's own counters the per-layer metrics read: the kind's,
    and the request cache's (whatever the kind, it may answer nothing)."""
    out = dict(deployment.counters(client))
    out.update({f"request_cache.{k}": v
                for k, v in client.node.request_cache.stats().items()
                if isinstance(v, (int, float))})
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


# ---------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------

def send(client, kind: str, specs: list) -> list:
    """One request. -> its responses, one per query spec."""
    if kind == "search":
        return [client.search(INDEX, specs[0]["body"])]
    lines = []
    for s in specs:
        lines += [{"index": INDEX}, s["body"]]
    return client.msearch(lines)["responses"]


def warm_up(client, stream, traffic: dict, pool: list,
            meter: CompileMeter) -> dict:
    """One pass over the window's own pool, request by request in the
    window's grouping, every query as its twin (the kind's
    `stream.twin`: another body of the same compiled shapes). The
    program's compiled shapes follow what a request touches (for a `match`
    the terms' posting lengths and, in a batch, how many of its queries
    climb which rung), so only the window's own requests are sure to
    compile what the window will use; the twins are other bodies, so the
    request cache answers nothing in the window. -> what it compiled, and
    which requests compiled (the late ones tell how rare a shape is)."""
    batch, kind = int(traffic["batch"]), traffic["request"]
    m0, t0, sent, compiled_at = meter.mark(), time.time(), 0, []
    for lo in range(0, len(pool) - batch + 1, batch):
        m1, t1 = meter.mark(), time.time()
        send(client, kind, [stream.twin(q) for q in pool[lo: lo + batch]])
        new = meter.since(m1)["programs"]
        if new:
            compiled_at.append([sent, new, round(time.time() - t1, 3)])
        sent += 1
    out = dict(meter.since(m0), requests=sent, seconds=time.time() - t0,
               request_programs_seconds=compiled_at)
    note(f"warm-up: {sent} requests, {out['programs']} programs, "
         f"{out['seconds']:.1f} s")
    return out


# ---------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------

class Window:
    """The load: one caller in this process, in a closed loop: it sends its
    next request when the last one answered. The window runs from the first
    send to the completion of the last request started before `seconds` was
    up, or to the end of the warmed pool where that comes first: every
    request of the window was warmed, and a run that reaches the pool's end
    did the same work as every other that does."""

    def __init__(self, client, traffic: dict, pool: list, seconds: float,
                 tracer=None):
        if traffic["loop"] != "closed" or int(traffic["clients"]) != 1:
            raise SystemExit("benchmark: only the closed loop with one "
                             "client is built; the PR that adds a cell "
                             "with another brings its path (PERF.md)")
        self.client, self.seconds, self.tracer = client, seconds, tracer
        self.kind, self.batch = traffic["request"], int(traffic["batch"])
        self.requests = [pool[lo: lo + self.batch] for lo in
                         range(0, len(pool) - self.batch + 1, self.batch)]
        self.records = []       # (start_s, latency_s, specs, responses|None)
        self.gc_pauses = []     # (start_s, seconds) of every collection
        self.window_s, self.ended_by = 0.0, "pool"

    def _gc(self, phase, _info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        else:
            self.gc_pauses.append((self._gc_t - self._t0,
                                   time.perf_counter() - self._gc_t))

    def run(self) -> None:
        import jax
        gc.callbacks.append(self._gc)
        self._t0 = t_done = time.perf_counter()
        try:
            for specs in self.requests:
                t_send = time.perf_counter()
                if t_send - self._t0 >= self.seconds:
                    self.ended_by = "seconds"
                    break
                try:
                    with jax.profiler.TraceAnnotation("bench.request"):
                        resps = send(self.client, self.kind, specs)
                except Exception as e:  # a failed request, counted below
                    note(f"request raised {type(e).__name__}: {e}")
                    resps = None
                t_done = time.perf_counter()
                self.records.append((t_send - self._t0, t_done - t_send,
                                     specs, resps))
                if self.tracer is not None:
                    self.tracer.after_request(t_done)
        finally:
            gc.callbacks.remove(self._gc)
        self.window_s = t_done - self._t0

    # -- read-outs --------------------------------------------------------

    def summary(self) -> dict:
        ok = [r for r in self.records if r[3] is not None
              and not any("error" in x for x in r[3])]
        lat = np.asarray([r[1] for r in ok]) * 1e3
        slow = sorted(self.records, key=lambda r: -r[1])[:3]
        out = {"attempted": len(self.records),
               "failed": len(self.records) - len(ok),
               "requests": len(ok), "queries": len(ok) * self.batch,
               "window_s": self.window_s, "ended_by": self.ended_by,
               "pool_requests": len(self.requests),
               "latency_samples": int(len(lat)),
               # where a stall sat: [start s, latency ms, weight] of the
               # slowest requests (a `match`'s weight is its term count),
               # and the collector's pauses
               "slowest": [[r[0], r[1] * 1e3, sum(q["weight"] for q in r[2])]
                           for r in slow],
               "gc": {"collections": len(self.gc_pauses),
                      "pause_ms_total": 1e3 * sum(p[1] for p in
                                                  self.gc_pauses),
                      "longest": [[p[0], p[1] * 1e3] for p in sorted(
                          self.gc_pauses, key=lambda p: -p[1])[:3]]}}
        if len(lat):
            out["latency_ms"] = {"p50": float(np.percentile(lat, 50)),
                                 "p95": float(np.percentile(lat, 95)),
                                 "max": float(lat.max()),
                                 "mean": float(lat.mean())}
        return out

    def answered(self) -> list:
        """(spec, response) of every query of every request that answered."""
        return [(s, r) for _t, _lat, specs, resps in self.records
                if resps is not None for s, r in zip(specs, resps)]


class Tracer:
    """The profiler over the window's first slice: until `min_requests`
    answered and `min_seconds` passed (traces are large)."""

    def __init__(self, out_dir: str, slice_: dict):
        self.dir, self.slice = out_dir, slice_
        self.requests, self.t0, self.active = 0, None, False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # TraceAnnotations, not every call
        opts.enable_hlo_proto = False   # the reduction reads no HLO
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active, self.t0 = True, time.perf_counter()

    def after_request(self, now: float) -> None:
        if not self.active:
            return
        self.requests += 1
        if (self.requests >= self.slice["min_requests"]
                and now - self.t0 >= self.slice["min_seconds"]):
            self.stop()

    def stop(self) -> None:
        import jax
        if self.active:
            self.active = False
            jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> str:
    """The `.xplane.pb` the profiler wrote under `trace_dir`."""
    for base, _dirs, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(base, f)
    raise SystemExit(f"benchmark: the profiler wrote no trace in {trace_dir}")


# ---------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------

def read_layer_metric(name: str, ctx: dict):
    """`read(ctx)` of `layer_metrics/<name>.py`; None where it finds
    nothing to read."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: per-layer metric {name!r} has no "
                         f"reader at {path}")
    return _load_module(path, f"layer_metric_{name}").read(ctx)


def end_to_end(summary: dict, setup_s: float, wanted: list) -> dict:
    lat, n = summary.get("latency_ms", {}), summary["latency_samples"]
    have = {"setup_s": setup_s}
    if summary["window_s"] > 0:
        have["qps"] = summary["queries"] / summary["window_s"]
    if "p50" in lat:
        have["p50_ms"] = lat["p50"]
    if n >= 200:                # ten samples or more beyond the 95th
        have["p95_ms"] = lat["p95"]
    return {m["name"]: {"value": have[m["name"]], "unit": m["unit"]}
            for m in wanted if m["name"] in have}


def check(deployment, client, window: Window, stream, built: dict,
          config: dict, traffic: dict, seed: int) -> dict:
    """After the window, held to the kind's reference by its rule: a seeded
    sample of the window's answered queries, and `check_fresh` requests of
    queries drawn from `--seed` that the pool does not hold, sent now
    (untimed) through the window's own entry, index and programs."""
    pairs = window.answered()
    n = min(int(traffic["check_sample"]), len(pairs))
    pick = np.random.default_rng([seed, 3]).choice(len(pairs), n,
                                                   replace=False)
    held = [pairs[i] for i in sorted(pick)]
    stream.reseed(seed)
    for _ in range(int(traffic["check_fresh"])):
        specs = stream.take(window.batch)
        try:
            resps = send(client, window.kind, specs)
        except Exception as e:
            resps = [{"error": f"{type(e).__name__}: {e}"}] * len(specs)
        held += list(zip(specs, resps))
    t0 = time.time()
    out = deployment.hold(held, built, config, traffic)
    out.update(from_the_window=n, fresh=len(held) - n,
               reference_s=time.time() - t0)
    return out


def run_cell(loaded: dict, seed: int, seconds: float, trace: bool,
             device: dict, meter: CompileMeter, out_dir: str) -> dict:
    """Everything after the look for the chip. -> the result object."""
    from opensearch_tpu.rest.client import RestClient
    config, traffic = loaded["config"], loaded["traffic"]
    deployment = load_kind(config.get("deployment_kind", DEFAULT_KIND))
    client = RestClient()
    built = deployment.build(config, seed, client, INDEX)
    note(f"{config['name']} built ({built['build_s']:.1f} s) and on the "
         f"device ({built['promote_s']:.1f} s)")
    # the pool is the traffic file's own (`pool_seed`): every seed sends the
    # same requests, in another order
    stream = deployment.stream(built, traffic, int(traffic["pool_seed"]))
    n, batch = int(traffic["pool_requests"]), int(traffic["batch"])
    drawn = stream.take(n * batch)
    pool = [q for r in np.random.default_rng([seed, 1]).permutation(n)
            for q in drawn[r * batch: (r + 1) * batch]]
    warm = warm_up(client, stream, traffic, pool, meter)

    tracer = Tracer(os.path.join(out_dir, "trace"),
                    traffic["trace"]) if trace else None
    window = Window(client, traffic, pool, seconds, tracer)
    c0, m0 = counters(deployment, client), meter.mark()
    if tracer:
        tracer.start()
    setup_s = time.time() - T_START
    window.run()
    if tracer:
        tracer.stop()
    in_window = {"counters": delta(counters(deployment, client), c0),
                 "compile": meter.since(m0)}
    summary = window.summary()
    note(f"window: {summary['requests']} requests in "
         f"{summary['window_s']:.2f} s; checking")
    emit({"readout": "window", **summary, **in_window,
          "warmup": warm, "build_s": built["build_s"],
          "promote_s": built["promote_s"], **built["readout"]})

    verdict = check(deployment, client, window, stream, built, config,
                    traffic, seed)
    emit({"readout": "check", **verdict})
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    result = {"correct": verdict["correct"],
              "attempted": summary["attempted"], "failed": summary["failed"]}
    if not trace:
        result["metrics"] = end_to_end(summary, setup_s,
                                       loaded["end_to_end"])
        result["device"] = device
        result["compared"] = verdict["numbers"]
        return result

    import trace_reduce
    reduced = trace_reduce.reduce_file(find_xplane(tracer.dir))
    emit({"readout": "trace", "traced_requests": tracer.requests,
          **{k: v for k, v in reduced.items() if k != "breakdown"}})
    ctx = {"window": dict(summary, **in_window), "warmup": warm,
           "setup": {"setup_s": setup_s, "build_s": built["build_s"],
                     "promote_s": built["promote_s"]},
           "trace": dict(reduced, queries=reduced["requests"]
                         * int(traffic["batch"])),
           "memory": {"peak_bytes": device["memory_peak_bytes"]},
           "peaks": loaded["peaks"].get(device["kind"])}
    if ctx["peaks"] is None:
        raise SystemExit(f"benchmark: peaks.json has no device kind "
                         f"{device['kind']!r}")
    metrics = {}
    for m in loaded["per_layer"]:
        value = read_layer_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dict(device, busy_s=reduced["busy_s"],
                            window_s=reduced["window_s"])
    result["breakdown"] = reduced["breakdown"]
    result["compared"] = verdict["numbers"]
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    loaded = load_cell(args.workload)
    device = require_device(int(loaded["cell"]["chips"]))
    import jax
    from opensearch_tpu.utils.compile_cache import place_compile_cache
    cache = place_compile_cache()
    # every program goes to the cache, the quick ones too: the second run
    # of a cell in a checkout then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    meter = CompileMeter()
    out_dir = os.path.join(ROOT, "benchmark_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    emit({"readout": "start", "workload": args.workload, "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace, "device": device,
          "compile_cache": cache})
    result = run_cell(loaded, args.seed, args.seconds, bool(args.trace),
                      device, meter, out_dir)
    emit(result)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
